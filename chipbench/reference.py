"""The plain reference that decides ``correct``, and its lower-precision
control. It imports nothing of the program and takes nothing the program
made except the answers it judges.

DirectLiNGAM, written out (Shimizu et al. 2011; paper Eqs. 7-8):

* State. After the roots ``order[:k]`` are removed, each remaining variable
  is its residual after least-squares regression on those roots. One float64
  QR of the centered data in the answer's order gives every such state:
  with ``X[order]^T = Q R``, the residual of ``order[t]`` after ``k`` roots
  is ``sum_{s=k..t} R[s, t] Q[:, s]``. Each residual is standardized.
* Score. For standardized rows ``u_i``, ``u_j``: ``c = cov(u_i, u_j)``,
  ``r_{i|j} = u_i - c u_j`` re-standardized from its samples,
  ``I_ij = H(u_j) + H(r_{i|j}) - H(u_i) - H(r_{j|i})`` with Hyvarinen's
  entropy approximation ``H``, and ``S_i = sum_j min(0, I_ij)^2`` over the
  live variables. The root is ``argmin S``.
* Strengths. ``B = I - L^{-1}`` in causal order, with ``L = R^T D^{-1}``
  the unit lower-triangular factor of the same QR (``D = diag R``).

The numbers compared:

* ``order_gap``: at each checked iteration, how far the answer's pick lies
  above the reference's best, ``(sqrt(S[pick]) - sqrt(min S)) / sqrt(live)``
  (``sqrt S`` moves by at most one unit per unit of error in the pair
  statistics, so this is a per-pair error scale); the largest over the
  checked iterations.
* ``b_err``: ``max |B - B_ref| / max |B_ref|``.

Scores run on the accelerator in float32 with full-precision matrix
products; state and strengths on the host in float64. The control is the
same reference one precision step down (``control=True``): the
elementwise score work in bfloat16 with float32 sums and the state's
float32 products in three bfloat16 passes (``high``) in place of full
precision; the strengths from bfloat16 data with one-pass products (a
``high`` QR reads closer to float64 than the program's float32 Cholesky
does, so it separates nothing; PERF.md gives both readings).
"""

from __future__ import annotations

import math
from functools import lru_cache

import numpy as np

K1 = 79.047
K2 = 7.4129
BETA = 0.37457
H_GAUSS = 0.5 * (1.0 + math.log(2.0 * math.pi))
#: Row block of the score sweep: 8 rows against all live rows at once.
ROW_BLOCK = 8


def qr_state(x: np.ndarray, order) -> tuple[np.ndarray, np.ndarray]:
    """Float64 ``(Q, R)`` of the centered ``x`` (p, n) with rows in
    ``order``."""
    xc = x - x.mean(axis=1, keepdims=True)
    q, r = np.linalg.qr(xc[np.asarray(order)].T)
    return q, r


def b_from_qr(r: np.ndarray, order) -> np.ndarray:
    """Float64 strengths ``B`` (p, p) in the original variable ids."""
    lower = (r / np.diag(r)[:, None]).T  # L[t, s] = R[s, t] / R[s, s]
    p = r.shape[0]
    b_ord = np.eye(p) - np.linalg.inv(lower)
    b_ord[np.triu_indices(p)] = 0.0
    order = np.asarray(order)
    b = np.zeros_like(b_ord)
    b[np.ix_(order, order)] = b_ord
    return b


def bucket(m: int) -> int:
    """Padded row count for ``m`` live rows: a multiple of 32 up to 128,
    then of 128, so a whole fit compiles a handful of score programs."""
    unit = 32 if m <= 128 else 128
    return -(-m // unit) * unit


def _entropy(m1, m2):
    return H_GAUSS - K1 * (m1 - BETA) ** 2 - K2 * m2 ** 2


def _row_entropy(u, dtype):
    """Hyvarinen entropy of each row of ``u`` after re-standardizing it from
    its samples; elementwise in ``dtype``, sums in float32."""
    import jax.numpy as jnp

    n = u.shape[-1]
    u = u.astype(dtype)
    mean = jnp.mean(u, axis=-1, keepdims=True, dtype=jnp.float32)
    c = (u - mean.astype(dtype))
    var = jnp.sum(jnp.square(c), axis=-1, keepdims=True,
                  dtype=jnp.float32) / (n - 1)
    z = c * (1.0 / jnp.sqrt(jnp.maximum(var, 1e-12))).astype(dtype)
    a = jnp.abs(z)
    log_cosh = a + jnp.log1p(jnp.exp(-2.0 * a)) - math.log(2.0)
    m1 = jnp.mean(log_cosh, axis=-1, dtype=jnp.float32)
    m2 = jnp.mean(z * jnp.exp(-0.5 * z * z), axis=-1, dtype=jnp.float32)
    return _entropy(m1, m2)


@lru_cache(maxsize=None)
def _score_fn(control: bool):
    """Jitted ``(q, rs, live) -> S``: the state's rows ``(q @ rs)^T`` and
    their score vector, padded rows masked out."""
    import jax
    import jax.numpy as jnp

    dtype = jnp.bfloat16 if control else jnp.float32
    prec = jax.lax.Precision.HIGH if control else jax.lax.Precision.HIGHEST

    def scores(q, rs, live):
        u = jnp.matmul(q, rs, precision=prec).T  # (M, n) standardized rows
        mm, n = u.shape
        hx = _row_entropy(u, dtype)
        cov = jnp.matmul(u, u.T, precision=prec) / (n - 1)
        ud = u.astype(dtype)

        def block(i0):
            ui = jax.lax.dynamic_slice_in_dim(ud, i0, ROW_BLOCK, 0)
            ci = jax.lax.dynamic_slice_in_dim(cov, i0, ROW_BLOCK, 0)
            r = ui[:, None, :] - ci.astype(dtype)[:, :, None] * ud[None]
            return _row_entropy(r, dtype)  # (ROW_BLOCK, M): H(r_{i|j})

        hr = jax.lax.map(block, jnp.arange(0, mm, ROW_BLOCK)).reshape(mm, mm)
        stat = (hx[None, :] - hx[:, None]) + (hr - hr.T)
        pair = live[:, None] & live[None, :] & ~jnp.eye(mm, dtype=bool)
        s = jnp.sum(jnp.where(pair, jnp.square(jnp.minimum(0.0, stat)), 0.0),
                    axis=1)
        return jnp.where(live, s, jnp.inf)

    return jax.jit(scores)


def state_operands(q: np.ndarray, r: np.ndarray, k: int):
    """Padded float32 operands of the state after ``k`` roots:
    ``q`` (n, M) and ``rs`` (M, M), the residual rows being
    ``(q @ rs)^T`` standardized, and the (M,) live mask."""
    n, p = q.shape
    m = p - k
    mm = bucket(m)
    rk = r[k:, k:]
    norm = np.sqrt(np.sum(rk * rk, axis=0) / (n - 1))
    qp = np.zeros((n, mm), np.float32)
    qp[:, :m] = q[:, k:]
    rs = np.zeros((mm, mm), np.float32)
    rs[:m, :m] = rk / norm[None, :]
    live = np.arange(mm) < m
    return qp, rs, live


def scores(q, r, k: int, control: bool = False) -> np.ndarray:
    """Reference (or control) score vector of the ``p - k`` live variables
    after the answer's first ``k`` roots, in the answer's order."""
    qp, rs, live = state_operands(q, r, k)
    s = np.asarray(_score_fn(control)(qp, rs, live))
    return s[: r.shape[0] - k].astype(np.float64)


def gap(s: np.ndarray, pick: int) -> float:
    """How far ``pick`` lies above the best of the score vector ``s``,
    per sqrt(live): ``(sqrt(s[pick]) - sqrt(min s)) / sqrt(len(s))``."""
    return float((math.sqrt(s[pick]) - math.sqrt(s.min())) / math.sqrt(len(s)))


def is_permutation(order, p: int) -> bool:
    return sorted(int(v) for v in order) == list(range(p))


def check_answer(x: np.ndarray, order, b, iterations, control: bool = False
                 ) -> dict:
    """The compared numbers of one answer ``(order, b)`` for data ``x``:
    ``order_gap`` over the listed ``iterations`` (the answer's pick at
    position k is its k-th root, index 0 in the state's live order) and
    ``b_err``. An order that is not a permutation reads ``inf`` on both.
    With ``control`` the reference's own lower-precision picks are judged
    instead of the answer's, in the same states."""
    p = x.shape[0]
    if not is_permutation(order, p) or not np.isfinite(b).all():
        return {"order_gap": math.inf, "b_err": math.inf}
    q, r = qr_state(x, order)
    worst = 0.0
    for k in iterations:
        s = scores(q, r, k)
        pick = int(np.argmin(scores(q, r, k, control=True))) if control else 0
        worst = max(worst, gap(s, pick))
    if control:
        b = control_b(x, order)
    b_ref = b_from_qr(r, order)
    err = float(np.abs(np.asarray(b, np.float64) - b_ref).max()
                / np.abs(b_ref).max())
    return {"order_gap": worst, "b_err": err}


def control_b(x: np.ndarray, order, step: str = "bfloat16") -> np.ndarray:
    """The strengths computed as the reference computes them, in float32 on
    the accelerator one precision step down: ``"bfloat16"`` rounds the
    centered data to bfloat16 and runs the QR's products in one bfloat16
    pass (the accelerator's default); ``"high"`` keeps float32 data and runs
    the products in three bfloat16 passes."""
    import jax
    import jax.numpy as jnp

    xc = x - x.mean(axis=1, keepdims=True)
    a = jnp.asarray(xc[np.asarray(order)].T, jnp.float32)
    if step == "bfloat16":
        a = a.astype(jnp.bfloat16).astype(jnp.float32)
    with jax.default_matmul_precision("default" if step == "bfloat16"
                                      else step):
        _, r = jnp.linalg.qr(a)
        r = np.asarray(r, np.float64)
    return b_from_qr(r, order)
