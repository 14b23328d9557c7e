"""``chip_smoke.py`` off the chip: it refuses a CPU-only JAX at its device
check, and its phases run and check correctly at small shapes (where
``auto`` resolves to ``xla`` and no kernel runs natively)."""

import importlib.util
import pathlib

import numpy as np
import pytest

_PATH = pathlib.Path(__file__).resolve().parents[1] / "chip_smoke.py"


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", _PATH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("argv", [[], ["--four-chip"]], ids=["one", "four"])
def test_refuses_without_tpu(smoke, argv, capsys):
    with pytest.raises(smoke.SmokeError, match="no TPU"):
        smoke.main(argv)
    assert '"ok"' not in capsys.readouterr().out


def test_phase_fit_small(smoke):
    dense, raw, thr = smoke.phase_fit(24, 2000, 0, expect_kernel=False)
    assert "order vs xla: identical" in dense
    assert "sqrt-score err per sqrt(live)" in dense and "raw SEM" in raw
    assert "fit threshold" in thr and "saved" in thr


def test_phase_fit_batch_small(smoke):
    (line,) = smoke.phase_fit_batch(12, 1000, 0, batch=3)
    assert "3/3 orders identical" in line


def test_phase_engine_small(smoke):
    (line,) = smoke.phase_engine([(10, 600), (12, 500), (20, 1200)], 0)
    assert "3/3 requests delivered" in line and "kernel_bypass=0" in line
    assert "3/3 orders identical" in line


def test_phase_quickstart(smoke):
    (line,) = smoke.phase_quickstart(*smoke.QUICKSTART)
    assert "auto=True xla=True" in line


def test_score_gap_refuses_a_wrong_order(smoke):
    """Swapping the first root with the last variable is no near-tie."""
    from repro import fit

    x = smoke.make_data(10, 2000, 1)["x"]
    order = fit(x)[0].order
    assert smoke.score_gap(x, order, order) == "identical"
    wrong = [order[-1]] + order[1:-1] + [order[0]]
    with pytest.raises(smoke.SmokeError, match="beyond a near-tie"):
        smoke.score_gap(x, wrong, order)


def test_close_b_bounds(smoke):
    b = np.eye(3)
    assert smoke.close_b(b, b) == 0.0
    with pytest.raises(smoke.SmokeError):
        smoke.close_b(b * 1.1, b)


def test_score_gap_refuses_a_1_5x_divergence(smoke):
    """Orders that part where the two candidates' first-iteration scores
    differ by about 1.5x are no near-tie."""
    x = smoke.make_data(24, 2000, 0)["x"]
    s = smoke.first_scores(x, "xla")
    pos = [int(v) for v in np.flatnonzero(s > 0)]
    a, b = min(((a, b) for a in pos for b in pos if a != b),
               key=lambda ab: abs(s[ab[1]] / s[ab[0]] - 1.5))
    assert 1.4 <= s[b] / s[a] <= 1.6
    rest = [v for v in range(24) if v not in (a, b)]
    with pytest.raises(smoke.SmokeError, match="beyond a near-tie"):
        smoke.score_gap(x, [b, a] + rest, [a, b] + rest)


@pytest.mark.parametrize("score,live", [(1.352185e-07, 53), (7.057e-03, 64)],
                         ids=["late_p85", "first_p64"])
def test_tie_gap_refuses_1_5x_scores(smoke, score, live):
    """At the score levels seen late in a p=85 order on the chip and in the
    first iteration at p=64, a 1.5x score gap is beyond the near-tie bound."""
    gap, bound = smoke.tie_gap(1.5 * score, score, live)
    assert gap > bound


def test_tie_gap_admits_the_chip_near_tie(smoke):
    """The fit_batch divergence a v5e showed (iteration 32 of 85) is within
    the bound."""
    gap, bound = smoke.tie_gap(1.354709e-07, 1.352185e-07, 85 - 32)
    assert gap <= bound


def test_sqrt_score_err(smoke):
    s = np.array([4.0, 9.0, 0.0, 1.0])
    assert smoke.sqrt_score_err(s, s) == 0.0
    assert smoke.sqrt_score_err(s + np.array([0, 0, 0.25, 0]), s) == 0.25
