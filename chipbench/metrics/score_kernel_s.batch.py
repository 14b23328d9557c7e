"""Device seconds of the score kernels (Pallas custom calls) per data set
fitted, averaged over the chips."""

from chipbench import trace


def read(tr: dict, ctx: dict) -> float | None:
    s = trace.kind_s(tr, "kernel")
    return None if s is None else s / ctx["datasets"]
