"""``correct`` comes out false where it should: for the control (the
reference one precision step down) and for each fault a cell's timed path
can have, with the rest of a run driven as on the chip."""

import json
import os
import subprocess
import sys
import types

import jax.numpy as jnp
import numpy as np
import pytest

import repro
from chipbench import run
from chipbench.tests.conftest import ROOT, make_tiny_root

SEED = 2**40 + 29


def program(**over):
    return types.SimpleNamespace(ParaLiNGAMConfig=repro.ParaLiNGAMConfig,
                                 fit=over.get("fit", repro.fit),
                                 fit_batch=over.get("fit_batch", repro.fit_batch))


@pytest.mark.parametrize("seed", [11, 12, 2**40 + 13])
def test_control_fails(tiny_root, seed):
    """The control's readings in the states of the program's own answers
    break the cell's limits, where the program's do not."""
    cell = run.load_cell(tiny_root, "ijr904.fit_dense")
    inputs = run.make_inputs(cell["cfg"], cell["mix"], seed)
    call = run.make_call(cell["mix"])
    answers = [((0, j), a) for j, a in enumerate(call(inputs[0]))]
    limits = cell["limits"]
    got = run.check(cell, inputs, answers, seed)["numbers"]
    assert all(got[k] <= limits[k] for k in limits), got
    ctl = run.check(cell, inputs, answers, seed, control=True)["numbers"]
    assert any(ctl[k] > limits[k] for k in limits), ctl


def test_answer_altered_fails(tiny_root):
    """The first two roots of every fit swapped where the order is made."""
    def fit(x, cfg):
        res, b = repro.fit(x, cfg)
        res.order = [res.order[1], res.order[0]] + list(res.order[2:])
        return res, b

    cell = run.load_cell(tiny_root, "ijr904.fit_dense")
    out = run.run(cell, SEED, 0.3, False, require_chip=False,
                  program=program(fit=fit))["result"]
    assert not out["correct"] and out["failed"] == out["attempted"]


def test_half_batch_left_out_fails(tiny_root):
    """Only the first half of each batch is fitted; its answers stand in for
    the rest."""
    def fit_batch(xs, cfg):
        half = repro.fit_batch(xs[: len(xs) // 2], cfg)
        return repro.core.paralingam.BatchFitResult(
            orders=np.concatenate([half.orders] * 2),
            comparisons=half.comparisons, rounds=half.rounds,
            converged=half.converged,
            b=jnp.concatenate([half.b] * 2))

    cell = run.load_cell(tiny_root, "ecoli_core.bootstrap100")
    out = run.run(cell, SEED, 0.3, False, require_chip=False,
                  program=program(fit_batch=fit_batch))["result"]
    assert not out["correct"] and out["failed"] > 0


RING_SCRIPT = """
import json, sys
from pathlib import Path
sys.path[:0] = [{root!r}, {src!r}]
import jax
if {broken}:
    jax.lax.ppermute = lambda x, axis_name, perm: x  # no exchange
from chipbench import run
cell = run.load_cell(Path({tiny!r}), "fig4_p1000.ring4_dense")
out = run.run(cell, {seed}, 0.3, False, require_chip=False)
print(json.dumps(out["result"]))
"""


@pytest.mark.parametrize("broken", [False, True])
def test_ring_exchange_left_out_fails(tmp_path, broken):
    """On four host devices: the ring cell is correct, and with every
    ppermute hop left out it is not."""
    tiny = make_tiny_root(tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    script = RING_SCRIPT.format(root=str(ROOT), src=str(ROOT / "src"),
                                tiny=str(tiny), seed=SEED, broken=broken)
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["correct"] is (not broken), res["checks"]
