"""The benchmark's copy of the Sec. 5.4 generator draws what the program's
``core.sem.generate`` draws, bit for bit."""

import numpy as np
import pytest

from chipbench import sem as bench_sem
from repro.core import sem


@pytest.mark.parametrize("p,n,seed", [(12, 5000, 42), (40, 2000, 0),
                                      (85, 1000, 7), (30, 500, 2**40 + 3)])
@pytest.mark.parametrize("density", ["sparse", "dense"])
@pytest.mark.parametrize("standardize", [False, True])
def test_sem_matches_program(p, n, seed, density, standardize):
    a = sem.generate(sem.SemSpec(p=p, n=n, density=density, seed=seed,
                                 standardize=standardize))
    b = bench_sem.generate(p, n, density, seed, standardize)
    assert np.array_equal(a["x"], b["x"])
    assert np.array_equal(a["b_true"], b["b_true"])
    assert [int(v) for v in a["order"]] == [int(v) for v in b["order"]]


def test_sem_seed_sequences_differ():
    a = bench_sem.generate(10, 100, "sparse", [5, 0, 0], True)["x"]
    b = bench_sem.generate(10, 100, "sparse", [5, 0, 1], True)["x"]
    assert not np.array_equal(a, b)
