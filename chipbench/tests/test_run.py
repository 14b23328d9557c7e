"""Runs of the harness: no TPU means no result; a cell added from files
alone runs; a run's line has the contract's keys."""

import hashlib
import json
import os
import shutil
import subprocess
import sys


from chipbench import run
from chipbench.tests.conftest import ROOT

SEED = 2**40 + 17


def _run_cli(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "ijr904.fit_dense",
         "--seed", "1", "--seconds", "1", "--trace", "0", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_no_result():
    out = _run_cli(ROOT)
    assert out.returncode != 0
    assert not out.stdout.strip()
    assert "TPU" in out.stderr


def test_no_program_no_result(tmp_path):
    """A directory holding only BENCHMARK.json and the benchmark's files."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = _run_cli(tmp_path)
    assert out.returncode != 0
    assert not out.stdout.strip()


def _digests(root):
    return {p.relative_to(root): hashlib.sha1(p.read_bytes()).hexdigest()
            for p in sorted((root / "chipbench").rglob("*")) if p.is_file()}


def test_cell_added_from_files_alone(tiny_root):
    """A new configuration, traffic mix, limits and manifest entry run with
    no file of the benchmark edited."""
    before = _digests(ROOT)
    bench = tiny_root / "chipbench"
    (bench / "configs" / "tiny_dense.json").write_text(json.dumps(
        {"name": "tiny_dense", "source": "test", "p": 10, "n": 600,
         "density": "dense", "standardize": True}))
    (bench / "traffic" / "two_sets.json").write_text(json.dumps(
        {"entry": "fit", "config": {}, "datasets": 2,
         "check": {"iterations": 4}}))
    (bench / "limits" / "tiny_dense.two_sets.json").write_text(json.dumps(
        {"order_gap": 1e-4, "b_err": 1e-3}))
    manifest = json.loads((tiny_root / "BENCHMARK.json").read_text())
    manifest["configs"].append({"name": "tiny_dense", "source": "test",
                                "file": "chipbench/configs/tiny_dense.json",
                                "reduced": [], "why": "test"})
    manifest["workloads"].append({"name": "tiny_dense.two_sets",
                                  "config": "tiny_dense", "traffic": "two_sets",
                                  "chips": 1, "why": "test"})
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if "ijr904.fit_dense" in m.get("workloads", []):
            m["workloads"].append("tiny_dense.two_sets")
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(manifest))
    cell = run.load_cell(tiny_root, "tiny_dense.two_sets")
    out = run.run(cell, SEED, 0.5, False, require_chip=False)
    res = out["result"]
    assert res["correct"], out["stderr"]
    assert set(res["metrics"]) == {"fit_s", "setup_s"}
    assert res["attempted"] >= 2
    assert _digests(ROOT) == before


def test_result_line_keys(tiny_root):
    cell = run.load_cell(tiny_root, "ijr904.fit_dense")
    out = run.run(cell, SEED, 0.5, False, require_chip=False)
    res = out["result"]
    assert list(res) == ["correct", "attempted", "failed", "metrics",
                         "device", "checks"]
    assert res["correct"] and res["failed"] == 0, out["stderr"]
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(res["device"])
    for name, c in res["checks"].items():
        assert c["value"] <= c["limit"]
        assert any(line.startswith(name) for line in out["stderr"])
    for m in res["metrics"].values():
        assert m["value"] > 0


def test_traced_line_keys(tiny_root):
    cell = run.load_cell(tiny_root, "ecoli_core.bootstrap100")
    out = run.run(cell, SEED, 0.5, True, require_chip=False)
    res = out["result"]
    assert res["correct"], out["stderr"]
    assert list(res)[-2:] == ["breakdown", "checks"]
    assert {"busy_s", "window_s"} <= set(res["device"])
    assert res["attempted"] % 6 == 0
    # a CPU trace has no device plane: no per-layer metric is read
    assert res["metrics"] == {}


def test_same_seed_same_inputs():
    cfg = {"p": 8, "n": 50, "density": "sparse", "standardize": True}
    mix = {"entry": "fit_batch", "datasets": 2, "resamples": 3}
    a = run.make_inputs(cfg, mix, SEED)
    b = run.make_inputs(cfg, mix, SEED)
    c = run.make_inputs(cfg, mix, SEED + 1)
    assert all((x == y).all() for x, y in zip(a, b))
    assert not (a[0] == c[0]).all()
    assert a[0].shape == (3, 8, 50) and not (a[0] == a[1]).all()
