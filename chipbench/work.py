"""What the algorithm has to compute, counted from the shapes alone.

A dense fit of ``p`` variables runs ``p - 1`` find-root iterations; the one
with ``m`` live variables compares every unordered live pair once (the
paper's messaging: one evaluation credits both endpoints) over all ``n``
samples, and each comparison needs both residuals ``r_{i|j}`` and
``r_{j|i}``. So the work of a fit is

    pair_samples(p, n) = n * sum_{m=2..p} m (m - 1) / 2 = n * C(p + 1, 3)

unordered live pair-samples, whatever buckets, padding, tiles or backend
run it. Per residual and sample, ``repro/core/entropy.py``'s formula needs
three transcendentals (``exp(-2|u|)``, ``log1p``, ``exp(-u^2/2)``) and the
vector operations counted in ``VPU_OPS_PER_RESIDUAL``; the bytes are one
read of each live row per iteration.
"""

from __future__ import annotations

#: Vector operations per residual and sample: the residual
#: ``(x_i - c x_j) * s`` (3), ``log cosh u = |u| + log1p(exp(-2|u|))`` past
#: its transcendentals (3: abs, scale, add; the constant ``- log 2`` folds
#: out of the mean), ``u exp(-u^2/2)`` past its exp (3: square, scale,
#: multiply) and the two running sums (2).
VPU_OPS_PER_RESIDUAL = 11
EXP_PER_RESIDUAL = 2
LOG1P_PER_RESIDUAL = 1
RESIDUALS_PER_PAIR = 2
BYTES_PER_VALUE = 4  # float32


def pair_samples(p: int, n: int) -> int:
    """Unordered live pair-samples of one dense fit of ``p`` variables."""
    return n * (p + 1) * p * (p - 1) // 6


def row_bytes(p: int, n: int) -> int:
    """Bytes of one read of every live row, summed over the iterations."""
    return BYTES_PER_VALUE * n * sum(range(2, p + 1))


def least_seconds(p: int, n: int, peaks: dict) -> tuple[float, str]:
    """The least time one chip could take for a fit's pair work, and which
    ceiling binds (``vpu``, ``eup`` or ``hbm``). The transcendentals share
    one unit, so their times add."""
    res = RESIDUALS_PER_PAIR * pair_samples(p, n)
    bounds = {
        "vpu": res * VPU_OPS_PER_RESIDUAL / peaks["vpu_f32_ops_per_s"],
        "eup": res * (EXP_PER_RESIDUAL / peaks["exp_per_s"]
                      + LOG1P_PER_RESIDUAL / peaks["log1p_per_s"]),
        "hbm": row_bytes(p, n) / peaks["hbm_bytes_per_s"],
    }
    name = max(bounds, key=bounds.get)
    return bounds[name], name
