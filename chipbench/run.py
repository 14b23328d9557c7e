"""Run one benchmark cell once on the chips of this machine.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is the ``workloads`` entry of ``BENCHMARK.json`` named ``<name>``.
Everything it needs is found by name: its configuration in
``chipbench/configs/<config>.json`` (sizes, with ``source``), its traffic in
``chipbench/traffic/<traffic>.json`` (which entry point, how many data sets,
how to check), its limits in ``chipbench/limits/<name>.json``, and each
per-layer metric in ``chipbench/metrics/<metric>.py``. A new cell is new
files and entries; no file here changes.

A run: set-up (imports, data from ``--seed``, one whole warm call, which
compiles or loads the compile cache), then whole calls back to back for
``--seconds``, then the check of every distinct answer against the plain
reference (``chipbench/reference.py``). With ``--trace 1`` the window runs
under the profiler and the line carries the per-layer metrics; with
``--trace 0``, the end-to-end ones. The last line of standard output is one
JSON object; the last lines of standard error give each compared number
beside its limit. It exits non-zero, printing no result, where JAX finds no
TPU or fewer chips than the cell asks for.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()  # set-up is timed from here

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for _p in (ROOT, ROOT / "src"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

#: JAX's persistent compilation cache: a fixed directory in the checkout.
CACHE_NAME = ".chipbench_cache"


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def load_cell(root: Path, workload: str) -> dict:
    """The cell's manifest entry with its configuration, traffic, limits and
    per-layer metric names, all found by name under ``root``."""
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    cell = dict(cells[workload])
    bench = root / "chipbench"
    cell["cfg"] = json.loads((bench / "configs" / f"{cell['config']}.json")
                             .read_text())
    cell["mix"] = json.loads((bench / "traffic" / f"{cell['traffic']}.json")
                             .read_text())
    limits = bench / "limits" / f"{workload}.json"
    cell["limits"] = json.loads(limits.read_text()) if limits.exists() else {}
    cell["end_to_end"] = [m for m in manifest["end_to_end"]
                          if workload in m.get("workloads", [workload])]
    cell["per_layer"] = [m for m in manifest["per_layer"]
                         if workload in m.get("workloads", [workload])]
    cell["root"] = root
    return cell


def device_info(chips: int) -> dict:
    """Platform, kind and count of JAX's devices; raises ``NoChip`` unless
    they are ``chips`` or more TPUs."""
    import jax

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if info["platform"] != "tpu" or info["count"] < chips:
        raise NoChip(f"{chips} TPU chip(s) wanted, JAX found {info}")
    return info


def enable_cache(path: Path) -> None:
    import jax

    jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


# ---------------------------------------------------------------------------
# traffic: data sets from the seed, and the timed call
# ---------------------------------------------------------------------------


def make_inputs(cfg: dict, mix: dict, seed: int) -> list:
    """The data sets the window cycles through, float64 NumPy as a user's
    code hands them over. ``fit`` mixes: ``datasets`` SEM draws (p, n).
    ``fit_batch`` mixes: one SEM draw and ``datasets`` stacks of
    ``resamples`` column resamples of it, with replacement (B, p, n)."""
    import numpy as np

    from chipbench import sem

    def draw(*key):
        return sem.generate(cfg["p"], cfg["n"], cfg["density"],
                            [seed, *key], cfg["standardize"])["x"]

    if mix["entry"] == "fit":
        return [draw(0, s) for s in range(mix["datasets"])]
    base = draw(0, 0)
    sets = []
    for s in range(mix["datasets"]):
        rng = np.random.default_rng([seed, 1, s])
        idx = rng.integers(0, cfg["n"], size=(mix["resamples"], cfg["n"]))
        sets.append(np.ascontiguousarray(np.moveaxis(base[:, idx], 1, 0)))
    return sets


def make_call(mix: dict, program=None):
    """``call(x) -> [(order, B), ...]`` for one timed call, each answer on
    the host as NumPy. ``program`` stands in for the ``repro`` module (the
    fault tests break it underneath)."""
    import jax
    import numpy as np

    if program is None:
        import repro as program
    cfg = program.ParaLiNGAMConfig(**mix.get("config", {}))
    span = jax.profiler.TraceAnnotation

    if mix["entry"] == "fit":
        def call(x):
            with span("chipbench.call"):
                res, b = program.fit(x, cfg)
            with span("chipbench.readback"):
                return [(np.asarray(res.order), np.asarray(b))]
    elif mix["entry"] == "fit_batch":
        def call(xs):
            with span("chipbench.call"):
                out = program.fit_batch(xs, cfg)
            with span("chipbench.wait"):
                out.b.block_until_ready()
            with span("chipbench.readback"):
                orders, bs = np.asarray(out.orders), np.asarray(out.b)
            return list(zip(orders, bs))
    else:
        raise ValueError(f"unknown entry {mix['entry']!r}")
    return call


def mesh_context(mix: dict):
    """The ``jax.set_mesh`` context a ring mix asks for (pods, ring, model),
    else a no-op context."""
    import contextlib

    if "mesh" not in mix:
        return contextlib.nullcontext()
    import jax

    from repro.launch.mesh import make_ring_mesh

    return jax.set_mesh(make_ring_mesh(*mix["mesh"]))


def run_window(call, inputs, seconds: float) -> dict:
    """Whole calls back to back, cycling through ``inputs``. No call starts
    that the last call's time says would end past ``seconds``; one always
    runs. Returns the window's length, the calls and every answer with the
    index of its input."""
    answers, last, n_calls = [], 0.0, 0
    t0 = time.perf_counter()
    while True:
        now = time.perf_counter() - t0
        if n_calls and now + last > seconds:
            break
        i = n_calls % len(inputs)
        t1 = time.perf_counter()
        out = call(inputs[i])
        last = time.perf_counter() - t1
        answers += [((i, j), a) for j, a in enumerate(out)]
        n_calls += 1
    return {"window_s": time.perf_counter() - t0, "calls": n_calls,
            "answers": answers}


# ---------------------------------------------------------------------------
# correctness
# ---------------------------------------------------------------------------


def distinct_answers(answers) -> dict:
    """``{(input, slot, answer bytes digest): (answer, fits)}``: answers of
    one data set that came back identical are checked once."""
    import hashlib

    out = {}
    for key, (order, b) in answers:
        h = hashlib.sha1(order.tobytes() + b.tobytes()).hexdigest()
        k = key + (h,)
        if k in out:
            out[k][1] += 1
        else:
            out[k] = [(order, b), 1]
    return out


def check(cell: dict, inputs, answers, seed: int, control: bool = False
          ) -> dict:
    """Compare a sample of the distinct answers, drawn from ``seed``, with
    the reference: in each, the first iteration and ``iterations`` more
    drawn from ``seed``. Returns the compared numbers (largest over the
    sample), the fits whose answer failed and the sample's size."""
    import numpy as np

    from chipbench import reference

    mix, cfg, limits = cell["mix"], cell["cfg"], cell["limits"]
    p = cfg["p"]
    names = ("order_gap", "b_err")
    want = mix["check"]
    rng = np.random.default_rng([seed, 2])
    keys = sorted(distinct_answers(answers).items())
    n_ans = min(want.get("answers", len(keys)), len(keys))
    picked = sorted(rng.choice(len(keys), size=n_ans, replace=False))
    worst = {name: 0.0 for name in names}
    failed = 0
    for a in picked:
        (i, j, _), ((order, b), fits) = keys[a]
        x = inputs[i] if mix["entry"] == "fit" else inputs[i][j]
        its = sorted({0} | set(rng.choice(
            p - 1, size=min(want["iterations"], p - 1), replace=False)))
        got = reference.check_answer(x, order, b, its, control=control)
        if any(not got[k] <= limits.get(k, math.inf) for k in names):
            failed += fits
        for k in names:
            worst[k] = max(worst[k], got[k])
    return {"numbers": worst, "failed": failed, "checked": n_ans}


# ---------------------------------------------------------------------------
# per-layer metrics
# ---------------------------------------------------------------------------


def load_reader(root: Path, name: str):
    """``read(trace, ctx)`` of ``chipbench/metrics/<name>.py``."""
    path = root / "chipbench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def peaks_for(root: Path, kind: str) -> dict:
    """The ceilings of ``kind`` in ``chipbench/peaks.json``; an unknown
    device is an error."""
    table = json.loads((root / "chipbench" / "peaks.json").read_text())
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return {k: v["value"] for k, v in table[kind].items()}


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------


def profile_options():
    """The device's operations and the benchmark's own host spans; no
    Python call tracing and no HLO dumps, which would slow the host and
    fill the disk."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    opts.enable_hlo_proto = False
    return opts


def memory_peak(chips: int) -> int:
    import jax

    stats = [d.memory_stats() or {} for d in jax.devices()[:chips]]
    return max(int(s.get("peak_bytes_in_use", 0)) for s in stats)


def run(cell: dict, seed: int, seconds: float, trace: bool, *,
        require_chip: bool = True, program=None, t_start: float = T_START
        ) -> dict:
    """One run of ``cell``: the result object the last line prints, plus
    ``stderr`` lines. ``require_chip=False`` skips the look for a TPU (tests
    drive the rest of a run on the CPU); ``program`` replaces ``repro``."""
    import jax

    chips = cell["chips"]
    if require_chip:
        device = device_info(chips)
    else:
        d0 = jax.devices()[0]
        device = {"platform": d0.platform, "kind": d0.device_kind,
                  "count": len(jax.devices())}
    enable_cache(cell["root"] / CACHE_NAME)
    cfg, mix = cell["cfg"], cell["mix"]
    inputs = make_inputs(cfg, mix, seed)
    with mesh_context(mix):
        call = make_call(mix, program)
        call(inputs[0])  # warm: compiles, or loads the compile cache
        setup_s = time.perf_counter() - t_start
        logdir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
        if trace:
            jax.profiler.start_trace(logdir, profiler_options=profile_options())
        win = run_window(call, inputs, seconds)
        if trace:
            jax.profiler.stop_trace()
    device["memory_peak_bytes"] = memory_peak(chips)
    fitted = len(win["answers"])
    breakdown = None
    if trace:
        metrics, breakdown = traced(cell, logdir, device, fitted)
    else:
        values = {"setup_s": setup_s,
                  "fit_s": win["window_s"] / win["calls"],
                  "fits_per_s": fitted / win["window_s"]}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell["end_to_end"]}
    got = check(cell, inputs, win["answers"], seed)
    limits = cell["limits"]
    checks = {k: {"value": v, "limit": limits.get(k)}
              for k, v in got["numbers"].items()}
    correct = got["failed"] == 0 and all(
        c["limit"] is not None and c["value"] <= c["limit"]
        for c in checks.values())
    out = {"correct": correct, "attempted": fitted, "failed": got["failed"],
           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    lines = [f"checked {got['checked']} distinct answer(s) of {fitted} fits "
             f"in {win['calls']} call(s)"]
    lines += [f"{k}: {c['value']!r} (limit {c['limit']!r})"
              for k, c in checks.items()]
    return {"result": out, "stderr": lines}


def traced(cell: dict, logdir: str, device: dict, fitted: int):
    """The per-layer metrics and the breakdown from the window's trace, and
    ``busy_s``/``window_s`` into ``device``; the trace is deleted once
    read."""
    import shutil

    from chipbench import trace as tr_mod
    from chipbench import work

    try:
        tr = tr_mod.load(logdir)
    finally:
        shutil.rmtree(logdir, ignore_errors=True)
    cfg = cell["cfg"]
    least, _ = work.least_seconds(cfg["p"], cfg["n"],
                                  peaks_for(cell["root"], device["kind"]))
    ctx = {"datasets": fitted, "least_s": least, "chips": cell["chips"]}
    metrics = {}
    for m in cell["per_layer"]:
        v = load_reader(cell["root"], m["name"])(tr, ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    device["busy_s"] = tr_mod.busy_s(tr) or 0.0
    device["window_s"] = tr_mod.window_s(tr)
    return metrics, {"device_ops": tr_mod.top_ops(tr),
                     "idle_gaps": tr_mod.idle_gaps(tr)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(ROOT, args.workload)
    try:
        out = run(cell, args.seed, args.seconds, bool(args.trace))
    except NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    for line in out["stderr"]:
        print(line, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out["result"]), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
