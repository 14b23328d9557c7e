"""Seconds the program spent building executables or loading them from the
compile cache before the traced window began (its ``repro.compile`` spans
during set-up)."""

from chipbench import program_spans


def read(tr: dict, ctx: dict) -> float | None:
    return program_spans.span_s(tr, ("repro.compile",),
                                (-(1 << 62), tr["window"][0]))
