"""Executables the program built or loaded from the compile cache inside
the traced window (its ``repro.compile`` spans), per run."""

from chipbench import program_spans


def read(tr: dict, ctx: dict) -> float | None:
    n = program_spans.count_in_window(tr, "repro.compile")
    return None if n is None else float(n)
