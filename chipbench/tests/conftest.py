import json
import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for _p in (ROOT, ROOT / "src"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

#: Sizes a test run can hold, per configuration.
TINY = {"ijr904": (24, 1500), "ecoli_core": (12, 800), "fig4_p1000": (16, 512)}
#: Ceilings for the CPU, so that a traced test run can compute a roofline;
#: they describe no device.
CPU_PEAKS = {"vpu_f32_ops_per_s": 1e10, "exp_per_s": 1e9, "log1p_per_s": 1e9,
             "hbm_bytes_per_s": 1e10, "bf16_flops_per_s": 1e10}


def make_tiny_root(dest: Path, resamples: int = 6) -> Path:
    """A copy of the benchmark's data files under ``dest`` with every
    configuration shrunk to ``TINY`` and the CPU in the peak table."""
    shutil.copy(ROOT / "BENCHMARK.json", dest / "BENCHMARK.json")
    shutil.copytree(ROOT / "chipbench", dest / "chipbench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    for name, (p, n) in TINY.items():
        f = dest / "chipbench" / "configs" / f"{name}.json"
        cfg = json.loads(f.read_text())
        cfg.update(p=p, n=n)
        f.write_text(json.dumps(cfg))
    f = dest / "chipbench" / "traffic" / "bootstrap100.json"
    mix = json.loads(f.read_text())
    mix["resamples"] = resamples
    f.write_text(json.dumps(mix))
    f = dest / "chipbench" / "peaks.json"
    peaks = json.loads(f.read_text())
    peaks["cpu"] = {k: {"value": v, "source": "test"} for k, v in CPU_PEAKS.items()}
    f.write_text(json.dumps(peaks))
    return dest


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return make_tiny_root(tmp_path_factory.mktemp("chipbench"))
