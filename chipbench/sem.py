"""The paper's Section 5.4 synthetic LiNGAM data (a copy kept with the
benchmark, so that no change to the program can change its inputs).

  * sparse graphs: #parents ~ U[1, 0.2 p]; dense: U[0.25 p, 0.5 p]
  * nonzero causal strengths ~ U([-0.95, -0.5] u [0.5, 0.95])
  * exogenous noise: Gaussian through a signed power nonlinearity with
    exponent ~ U([0.5, 0.8] u [1.2, 2.0]) (non-Gaussian by construction)
  * samples generated recursively in causal order, each variable optionally
    rescaled to unit variance as it is generated, then variables randomly
    permuted.

Bit-for-bit the same draws as ``repro.core.sem.generate`` for the same
arguments (``chipbench/tests/test_sem.py``). ``seed`` is anything
``numpy.random.default_rng`` takes: an int of any size or a sequence of
them.
"""

from __future__ import annotations

import numpy as np


def random_adjacency(p: int, density: str, rng: np.random.Generator) -> np.ndarray:
    """Strictly lower-triangular causal strengths (identity causal order)."""
    b = np.zeros((p, p), dtype=np.float64)
    if density == "sparse":
        lo, hi = 1, max(1, int(0.2 * p))
    elif density == "dense":
        lo, hi = max(1, int(0.25 * p)), max(1, int(0.5 * p))
    else:
        raise ValueError(f"unknown density {density!r}")
    for i in range(1, p):
        k = min(int(rng.integers(lo, hi + 1)), i)
        parents = rng.choice(i, size=k, replace=False)
        mag = rng.uniform(0.5, 0.95, size=k)
        sign = rng.choice([-1.0, 1.0], size=k)
        b[i, parents] = mag * sign
    return b


def non_gaussian_noise(p: int, n: int, rng: np.random.Generator) -> np.ndarray:
    """(p, n) Gaussian draws through a per-variable signed power."""
    z = rng.standard_normal((p, n))
    q = np.where(rng.random(p) < 0.5,
                 rng.uniform(0.5, 0.8, size=p),
                 rng.uniform(1.2, 2.0, size=p))[:, None]
    return np.sign(z) * np.abs(z) ** q


def generate(p: int, n: int, density: str, seed, standardize: bool) -> dict:
    """``x`` (p, n) float64 observations with permuted variable ids,
    ``b_true`` (p, p) strengths in those ids, and ``order`` a valid causal
    order of them."""
    rng = np.random.default_rng(seed)
    b = random_adjacency(p, density, rng)
    noise = non_gaussian_noise(p, n, rng)
    x = np.zeros_like(noise)
    for i in range(p):
        x[i] = b[i, :i] @ x[:i] + noise[i]
        if standardize:
            s = x[i].std()
            x[i] /= s
            b[i] /= s
    perm = rng.permutation(p)
    x_perm = np.empty_like(x)
    x_perm[perm] = x
    b_perm = np.zeros_like(b)
    b_perm[np.ix_(perm, perm)] = b
    return {"x": x_perm, "b_true": b_perm, "order": list(perm)}
