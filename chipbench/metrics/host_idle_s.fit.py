"""Seconds per data set fitted in which the device ran nothing while the
host was in one of the program's host steps (hand-off, dispatch, the ring's
eager set-up, readback, compile), inside the traced window, averaged over
the chips."""

from chipbench import program_spans


def read(tr: dict, ctx: dict) -> float | None:
    s = program_spans.idle_under_s(tr, program_spans.HOST_STEPS)
    return None if s is None else s / ctx["datasets"]
