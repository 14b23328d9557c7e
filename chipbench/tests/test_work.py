"""The work count against a brute-force count, and the roofline bound."""

import pytest

from chipbench import work


def brute_pair_samples(p: int, n: int) -> int:
    """Enumerate the dense fit: iterations with m = p .. 2 live variables,
    every unordered live pair, every sample."""
    total, live = 0, list(range(p))
    while len(live) > 1:
        total += sum(1 for a in live for b in live if a < b) * n
        live.pop()
    return total


@pytest.mark.parametrize("p,n", [(2, 1), (3, 5), (8, 10), (17, 3), (85, 7)])
def test_pair_samples_brute_force(p, n):
    assert work.pair_samples(p, n) == brute_pair_samples(p, n)


def test_row_bytes_brute_force():
    p, n = 9, 11
    assert work.row_bytes(p, n) == 4 * n * sum(m for m in range(2, p + 1))


@pytest.mark.parametrize("slow,name", [("vpu_f32_ops_per_s", "vpu"),
                                       ("exp_per_s", "eup"),
                                       ("hbm_bytes_per_s", "hbm")])
def test_least_seconds_names_the_binding_ceiling(slow, name):
    peaks = {"vpu_f32_ops_per_s": 1e15, "exp_per_s": 1e15,
             "log1p_per_s": 1e15, "hbm_bytes_per_s": 1e15}
    peaks[slow] = 1.0
    secs, bound = work.least_seconds(64, 100, peaks)
    assert bound == name and secs > 1.0


def test_least_seconds_value():
    peaks = {"vpu_f32_ops_per_s": 22.0, "exp_per_s": 1e30,
             "log1p_per_s": 1e30, "hbm_bytes_per_s": 1e30}
    secs, bound = work.least_seconds(4, 1, peaks)
    # 10 pair-samples, 2 residuals each, 11 vector operations each
    assert bound == "vpu" and secs == pytest.approx(10 * 2 * 11 / 22.0)
