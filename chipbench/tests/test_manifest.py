"""BENCHMARK.json against the contract it is held to, and every file it
names found by name."""

import json
import re

import pytest

from chipbench.tests.conftest import ROOT

MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELLS = [w["name"] for w in MANIFEST["workloads"]]


def test_top_level_keys():
    assert list(MANIFEST) == ["command", "paths", "run_seconds", "configs",
                              "workloads", "end_to_end", "per_layer"]
    assert MANIFEST["paths"] == ["chipbench"]
    assert MANIFEST["command"][1].startswith("chipbench/")
    assert 1 <= MANIFEST["run_seconds"] <= 51
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


def test_check_fits_the_day():
    cells = 24
    runs = 2 + 14 * cells
    total = runs * (MANIFEST["run_seconds"] + 60) + cells * 2 * 90 + 1200
    assert total <= 43200


def test_names_units_and_keys():
    keys = {"configs": {"name", "source", "file", "reduced", "why"},
            "workloads": {"name", "config", "traffic", "chips", "why"},
            "end_to_end": {"name", "unit", "better", "bound", "source"},
            "per_layer": {"name", "unit", "better", "source", "layer", "moves"}}
    for section, want in keys.items():
        names = [e["name"] for e in MANIFEST[section]]
        assert len(names) == len(set(names))
        for e in MANIFEST[section]:
            assert NAME.match(e["name"]), e["name"]
            assert set(e) - {"workloads"} == want, e["name"]
            for k in ("why", "layer", "source"):
                if k in e:
                    assert 1 <= len(e[k]) <= 200 and "\n" not in e[k]
            if "unit" in e:
                assert UNIT.match(e["unit"]) and e["better"] in ("lower", "higher")


def test_cells():
    configs = {c["name"] for c in MANIFEST["configs"]}
    pairs = [(w["config"], w["traffic"]) for w in MANIFEST["workloads"]]
    assert len(pairs) == len(set(pairs))
    assert configs == {c for c, _ in pairs}
    assert CELLS == ["ijr904.fit_dense", "ecoli_core.bootstrap100",
                     "fig4_p1000.ring4_dense"]
    assert sum(w["chips"] == 4 for w in MANIFEST["workloads"]) <= 1
    for w in MANIFEST["workloads"]:
        assert w["chips"] in (1, 4)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_found_by_name(cell):
    w = next(w for w in MANIFEST["workloads"] if w["name"] == cell)
    bench = ROOT / "chipbench"
    cfg = json.loads((bench / "configs" / f"{w['config']}.json").read_text())
    assert {"p", "n", "density", "standardize", "source"} <= set(cfg)
    mix = json.loads((bench / "traffic" / f"{w['traffic']}.json").read_text())
    assert mix["entry"] in ("fit", "fit_batch") and "check" in mix
    limits = json.loads((bench / "limits" / f"{cell}.json").read_text())
    assert set(limits) == {"order_gap", "b_err"}
    for m in MANIFEST["per_layer"]:
        if cell in m.get("workloads", CELLS):
            assert (bench / "metrics" / f"{m['name']}.py").exists(), m["name"]


def test_config_files_under_paths_and_distinct():
    files = [c["file"] for c in MANIFEST["configs"]]
    assert len(files) == len(set(files))
    for c in MANIFEST["configs"]:
        assert c["file"] == f"chipbench/configs/{c['name']}.json"
        assert (ROOT / c["file"]).exists()


def test_every_cell_reports_setup_another_and_a_layer():
    e2e = {m["name"]: m for m in MANIFEST["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    for m in MANIFEST["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for cell in CELLS:
        reported = {n for n, m in e2e.items() if cell in m.get("workloads", CELLS)}
        assert "setup_s" in reported and len(reported) >= 2
        layers = [m for m in MANIFEST["per_layer"]
                  if cell in m.get("workloads", CELLS)]
        assert layers
        for m in layers:
            assert m["moves"] in reported, (cell, m["name"])


def test_layers_named_alike():
    layers = {m["layer"] for m in MANIFEST["per_layer"]}
    assert layers == {"device", "score kernels", "order driver and phase 2",
                      "ring"}


def test_peaks_have_sources():
    peaks = json.loads((ROOT / "chipbench" / "peaks.json").read_text())
    for kind, table in peaks.items():
        for name, entry in table.items():
            assert entry["value"] > 0 and entry["source"], (kind, name)
