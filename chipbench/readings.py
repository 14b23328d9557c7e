"""Read the compared numbers of a cell's timed path, and of its control,
over many seeds in one process: what its limits are set from.

    python3 chipbench/readings.py --workload <name> --seeds 1,2,3 [--control]

For each seed: the cell's data, one whole timed call (the first compiles),
the check of its answers against the reference as a run makes it, and with
``--control`` the same check of the reference's own lower-precision picks
and strengths in the same states (``reference.check_answer(control=True)``).
One JSON line per seed; exits non-zero off a TPU.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

from run import ROOT, CACHE_NAME, NoChip, check, device_info, enable_cache, \
    load_cell, make_call, make_inputs, mesh_context


def high_b_err(cell: dict, inputs, answers) -> float:
    """``b_err`` of the strengths from a float32 QR with three-pass
    products, for the first answer: the step above the control's."""
    import numpy as np

    from chipbench import reference

    (_, j), (order, _) = answers[0]
    x = inputs[0] if cell["mix"]["entry"] == "fit" else inputs[0][j]
    b_ref = reference.b_from_qr(reference.qr_state(x, order)[1], order)
    b = reference.control_b(x, order, step="high")
    return float(np.abs(b - b_ref).max() / np.abs(b_ref).max())


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)
    cell = load_cell(ROOT, args.workload)
    try:
        device_info(cell["chips"])
    except NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    enable_cache(ROOT / CACHE_NAME)
    with mesh_context(cell["mix"]):
        call = make_call(cell["mix"])
        for seed in (int(s) for s in args.seeds.split(",")):
            inputs = make_inputs(cell["cfg"], cell["mix"], seed)[:1]
            t0 = time.perf_counter()
            answers = [((0, j), a) for j, a in enumerate(call(inputs[0]))]
            t1 = time.perf_counter()
            line = {"seed": seed, "call_s": t1 - t0,
                    "program": check(cell, inputs, answers, seed)["numbers"]}
            line["check_s"] = time.perf_counter() - t1
            if args.control:
                line["control"] = check(cell, inputs, answers, seed,
                                        control=True)["numbers"]
                line["control_high_b_err"] = high_b_err(cell, inputs, answers)
            print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
