"""Share of the traced window in which the device ran nothing, in %, averaged
over the chips."""

from chipbench import trace


def read(tr: dict, ctx: dict) -> float | None:
    share = trace.idle_share(tr)
    return None if share is None else 100.0 * share
