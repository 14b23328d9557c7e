"""Host seconds per data set fitted spent handing the data to the device
(the program's ``repro.handoff`` spans: the host cast and the host's part of
the transfer) inside the traced window."""

from chipbench import program_spans


def read(tr: dict, ctx: dict) -> float | None:
    s = program_spans.span_s(tr, ("repro.handoff",))
    return None if s is None else s / ctx["datasets"]
