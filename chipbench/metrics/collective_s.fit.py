"""Device seconds of exchanges between chips per fit, averaged over the chips."""

from chipbench import trace


def read(tr: dict, ctx: dict) -> float | None:
    s = trace.kind_s(tr, "collective")
    return None if s is None else s / ctx["datasets"]
