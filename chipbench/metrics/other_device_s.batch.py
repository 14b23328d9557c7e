"""Device seconds per data set fitted in which neither a score kernel nor a
collective ran but another operation did (the order driver's updates and
compactions, normalization, covariance, phase-2 adjacency), averaged over
the chips."""

from chipbench import trace


def read(tr: dict, ctx: dict) -> float | None:
    s = trace.other_s(tr)
    return None if s is None else s / ctx["datasets"]
