"""Score kernels' share of their roofline, in %: the least time the cell's
chips could take for a fit's live pair work (chipbench/work.py at the
ceilings of chipbench/peaks.json), over the kernels' device seconds per data
set."""

from chipbench import trace


def read(tr: dict, ctx: dict) -> float | None:
    s = trace.kind_s(tr, "kernel")
    if not s:
        return None
    return 100.0 * ctx["least_s"] / (ctx["chips"] * s / ctx["datasets"])
