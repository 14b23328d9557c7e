"""Chip smoke test: ParaLiNGAM's main path, once, on a TPU, checked.

    python chip_smoke.py              # one chip: fit, fit_batch, engine
    python chip_smoke.py --four-chip  # only the messaging ring on four chips

The one-chip run fits the paper's IJR904 shape (p=770, n=10^4) through
``repro.fit`` with the default config (the fused Pallas score kernel on a
TPU), dense and with the threshold mechanism, against
``score_backend="xla"`` on the same chip; then a ``fit_batch`` of eight
ECOLI_CORE-shaped data sets (p=85, n=10^4) against dedicated fits, an
``AsyncLingamEngine`` answering requests in two buckets, and the quickstart
shape's order validity. ``--four-chip`` runs only the ring
(``order_backend="ring"`` under ``jax.set_mesh``) at the paper's Fig. 4
p=1000, n=8192 (see ``FIG4_P1000``), dense and threshold, against the
one-chip scan order.

Data comes from ``core.sem.generate`` and ``--seed``, standardized as it is
generated (``SemSpec.standardize``) except at the quickstart shape. Every
phase prints one summary line; any failed check raises, so the script exits
non-zero. It exits non-zero before any work when JAX finds no TPU. The last
line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
The compile cache goes to ``$JAX_COMPILATION_CACHE_DIR`` when set, else to
``<checkout>/.jax_cache``. The script runs in one process and starts none.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

#: First-iteration score vectors of two backends agree to this, relative to
#: the largest score: f32 moment sums over n=10^4 samples, summed in a
#: different order by the kernel and by XLA, then squared into the score.
SCORE_RTOL = 1e-3
#: How far one state's sqrt-scores may move between two score backends, per
#: sqrt(live variables). A score is S_i = sum_j min(0, I_ij)^2, so sqrt(S_i)
#: is 1-Lipschitz in the pair statistics I_ij: an error of at most TIE_EPS in
#: each moves it by at most TIE_EPS * sqrt(live). Two orders may part only
#: at a near-tie: the candidates' sqrt-scores (``xla``, on the state both
#: orders share) differ by at most twice that. Set with a margin over the
#: kernel-vs-``xla`` error of the first iteration at IJR904 on a v5e (PERF.md,
#: Findings, PR 12); the fit phase measures that error on every run and fails
#: where it exceeds TIE_EPS.
TIE_EPS = 1e-6
#: Strengths ``B`` of two fits with the same order agree to this, relative
#: to the largest entry.
B_RTOL = 1e-3

IJR904 = (770, 10_000)  # configs/lingam.py, ParaLiNGAM Table 1
#: The four-chip check runs at FIG4_P1000 (configs/lingam.py, the paper's
#: Fig. 4), not IML1515 (p=2326): IML1515's one-chip scan reference makes
#: ~2300 score sweeps, 278 of them at the 4096 bucket (16x the work of a
#: 1024-bucket sweep at n=10^4), and it holds all four chips while it runs.
FIG4_P1000 = (1000, 8192)
ECOLI_CORE = (85, 10_000)
QUICKSTART = (12, 5000, 42)  # examples/quickstart.py: p, n, seed


class SmokeError(AssertionError):
    """A phase's check failed."""


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeError(msg)


def device_info(need: int) -> dict:
    """Platform, kind and count of the devices JAX found; refuses anything
    but ``need`` or more TPUs."""
    import jax

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if info["platform"] != "tpu":
        raise SmokeError(f"no TPU: JAX found {info}")
    if info["count"] < need:
        raise SmokeError(f"{need} TPU chips wanted, JAX found {info}")
    return info


def make_data(p: int, n: int, seed: int, standardize: bool = True):
    """The paper's sparse SEM. Standardized as generated by default: the raw
    SEM's correlation matrix is singular in float64 from p in the hundreds,
    and scores of its collinear pairs measure rounding, not the method."""
    from repro.core import sem

    return sem.generate(sem.SemSpec(p=p, n=n, density="sparse", seed=seed,
                                    standardize=standardize))


def first_scores(x, score_backend: str):
    """The first iteration's full score vector under ``score_backend``."""
    import jax.numpy as jnp

    from repro.core.covariance import cov_matrix, normalize
    from repro.core.paralingam import find_root_dense

    xn = normalize(jnp.asarray(x, jnp.float32))
    c = cov_matrix(xn)
    _, s = find_root_dense(xn, c, jnp.ones((x.shape[0],), bool),
                           score_backend=score_backend)
    return np.asarray(s)


def score_gap(x, got, ref):
    """Compare two causal orders of ``x``. Returns ``"identical"`` or a
    description of the first divergence; raises unless that divergence is a
    near-tie (``TIE_EPS``) on the ``xla`` scores of the state after the
    shared prefix."""
    import jax.numpy as jnp

    from repro.core.covariance import cov_matrix, normalize
    from repro.core.paralingam import _update_iteration, find_root_dense

    got, ref = list(got), list(ref)
    check(sorted(got) == sorted(ref) == list(range(len(ref))),
          "orders are not permutations of the variables")
    if got == ref:
        return "identical"
    k = next(i for i, (a, b) in enumerate(zip(got, ref)) if a != b)
    xn = normalize(jnp.asarray(x, jnp.float32))
    c = cov_matrix(xn)
    mask = jnp.ones((len(ref),), bool)
    for root in ref[:k]:
        xn, c, mask = _update_iteration(xn, c, jnp.int32(root), mask)
    _, s = find_root_dense(xn, c, mask, score_backend="xla")
    sa, sb = float(s[got[k]]), float(s[ref[k]])
    gap, bound = tie_gap(sa, sb, len(ref) - k)
    desc = (f"first diverge at iteration {k} of {len(ref)}: "
            f"{got[k]} vs {ref[k]}, scores {sa:.6e} vs {sb:.6e}, "
            f"sqrt-score gap {gap:.3e} (near-tie bound {bound:.3e})")
    check(gap <= bound, f"orders diverge beyond a near-tie: {desc}")
    return desc


def tie_gap(sa: float, sb: float, live: int):
    """The sqrt-score gap of two candidate roots' scores among ``live``
    variables, and the largest gap a near-tie may have (``TIE_EPS``)."""
    return abs(np.sqrt(sa) - np.sqrt(sb)), 2 * TIE_EPS * np.sqrt(live)


def sqrt_score_err(s_got, s_ref) -> float:
    """Largest sqrt-score difference of two score vectors of one state, per
    sqrt(live variables): the quantity ``TIE_EPS`` bounds."""
    return float(np.abs(np.sqrt(s_got) - np.sqrt(s_ref)).max()
                 / np.sqrt(len(s_ref)))


def _tally(rules) -> str:
    """``k/N``, then each non-identical order's near-tie."""
    ties = [f"[{i}] {r}" for i, r in enumerate(rules) if r != "identical"]
    out = f"{len(rules) - len(ties)}/{len(rules)}"
    return out + (f" (near-ties: {'; '.join(ties)})" if ties else "")


def close_b(b_got, b_ref) -> float:
    b_got, b_ref = np.asarray(b_got), np.asarray(b_ref)
    check(np.isfinite(b_got).all(), "B has non-finite entries")
    err = float(np.abs(b_got - b_ref).max() / max(np.abs(b_ref).max(), 1e-30))
    check(err <= B_RTOL, f"B differs by {err:.3e} relative (> {B_RTOL})")
    return err


def has_kernel(x, config) -> bool:
    """Whether the program ``fit`` runs holds a native TPU kernel call."""
    from repro.core.paralingam import lower_fit

    return "tpu_custom_call" in lower_fit(x, config).compile().as_text()


def phase_fit(p: int, n: int, seed: int, *, expect_kernel: bool = True):
    """Dense and threshold fits, default config, against ``xla``; and the
    first iteration's kernel-vs-``xla`` error on the raw SEM, reported."""
    from repro import ParaLiNGAMConfig, fit

    x = make_data(p, n, seed)["x"]
    lines = []
    t0 = time.perf_counter()
    ref, b_ref = fit(x, ParaLiNGAMConfig(score_backend="xla"))
    np.asarray(b_ref)
    t_ref = time.perf_counter() - t0

    kernel = has_kernel(x, ParaLiNGAMConfig())
    check(kernel or not expect_kernel,
          "the default-config fit program holds no tpu_custom_call")
    s_k = first_scores(x, "auto")
    s_x = first_scores(x, "xla")
    check(np.isfinite(s_k).all() and np.isfinite(s_x).all(),
          "first-iteration scores are not finite")
    s_err = float(np.abs(s_k - s_x).max() / np.abs(s_x).max())
    check(s_err <= SCORE_RTOL,
          f"first-iteration scores differ by {s_err:.3e} (> {SCORE_RTOL})")
    eps = sqrt_score_err(s_k, s_x)
    check(eps <= TIE_EPS, f"first-iteration sqrt-score error {eps:.3e} per "
                          f"sqrt(live) exceeds TIE_EPS={TIE_EPS}")
    t0 = time.perf_counter()
    res, b = fit(x, ParaLiNGAMConfig())
    np.asarray(b)
    t_def = time.perf_counter() - t0
    rule = score_gap(x, res.order, ref.order)
    b_err = close_b(b, b_ref) if rule == "identical" else None
    check(np.isfinite(np.asarray(b)).all(), "B has non-finite entries")
    lines.append(
        f"fit dense p={p} n={n}: tpu_custom_call={kernel} "
        f"first-iteration score rel err {s_err:.3e} (<= {SCORE_RTOL}), "
        f"sqrt-score err per sqrt(live) {eps:.3e} (<= TIE_EPS {TIE_EPS}); "
        f"order vs xla: {rule}; B rel err "
        f"{'n/a' if b_err is None else f'{b_err:.3e}'}; B finite; "
        f"wall s (compile included) default {t_def:.1f}, xla {t_ref:.1f}")

    x_raw = make_data(p, n, seed, standardize=False)["x"]
    r_k, r_x = first_scores(x_raw, "auto"), first_scores(x_raw, "xla")
    lines.append(
        f"raw SEM p={p} n={n} (not standardized; reported, not checked): "
        f"first-iteration score rel err "
        f"{np.abs(r_k - r_x).max() / np.abs(r_x).max():.3e}, sqrt-score err "
        f"per sqrt(live) {sqrt_score_err(r_k, r_x):.3e}, largest score "
        f"{r_x.max():.3e}")

    t0 = time.perf_counter()
    thr, b_thr = fit(x, ParaLiNGAMConfig(threshold=True))
    np.asarray(b_thr)
    t_thr = time.perf_counter() - t0
    check(thr.converged, "threshold fit did not converge")
    check(np.isfinite(np.asarray(b_thr)).all(), "threshold B has non-finite entries")
    rule_t = score_gap(x, thr.order, ref.order)
    lines.append(
        f"fit threshold p={p} n={n}: order vs dense xla: {rule_t}; "
        f"comparisons {thr.comparisons} of serial {thr.comparisons_serial} "
        f"(saved {100 * thr.saving_vs_serial:.2f}%), rounds {thr.rounds}; "
        f"B finite; wall s (compile included) {t_thr:.1f}")
    return lines


def phase_fit_batch(p: int, n: int, seed: int, batch: int = 8):
    """``fit_batch`` of ``batch`` data sets against dedicated fits."""
    from repro import fit, fit_batch

    xs = np.stack([make_data(p, n, seed + i)["x"] for i in range(batch)])
    fb = fit_batch(xs)
    orders = np.asarray(fb.orders)
    bs = np.asarray(fb.b)
    rules, errs = [], []
    for i in range(batch):
        res, b = fit(xs[i])
        rule = score_gap(xs[i], orders[i], res.order)
        rules.append(rule)
        if rule == "identical":
            errs.append(close_b(bs[i], b))
        check(np.isfinite(bs[i]).all(), f"fit_batch B[{i}] is not finite")
    return [f"fit_batch {batch}x(p={p}, n={n}): {_tally(rules)} orders "
            f"identical to dedicated fits; max B rel err "
            f"{max(errs, default=0.0):.3e}"]


def phase_engine(shapes, seed: int):
    """An ``AsyncLingamEngine`` answers requests of mixed shape; every one
    is delivered and equals a dedicated fit."""
    from repro import AsyncLingamEngine, ParaLiNGAMConfig, fit
    from repro.serve.batching import BatchingConfig
    from repro.serve.lingam_engine import LingamServeConfig, bucket_shape

    xs = [make_data(p, n, seed + i)["x"] for i, (p, n) in enumerate(shapes)]
    scfg = LingamServeConfig()
    buckets = {bucket_shape(p, n, scfg) for p, n in shapes}
    check(len(buckets) >= 2, f"requests span only buckets {buckets}")
    with AsyncLingamEngine(ParaLiNGAMConfig(), scfg,
                           batch_cfg=BatchingConfig(flush_interval=0.5)) as eng:
        fits = eng.fit_many(xs, timeout=900)
        stats = eng.stats()
    check(stats["delivered"] == len(xs),
          f"{stats['delivered']} of {len(xs)} requests delivered")
    check(stats["kernel_bypass"] == 0, f"kernel_bypass={stats['kernel_bypass']}")
    rules = []
    for x, f in zip(xs, fits):
        res, b = fit(x)
        rule = score_gap(x, f.order, res.order)
        rules.append(rule)
        if rule == "identical":
            close_b(f.b, b)
    return [f"engine: {stats['delivered']}/{len(xs)} requests delivered over "
            f"buckets {sorted(buckets)}; {_tally(rules)} orders identical to "
            f"dedicated fits; kernel_bypass={stats['kernel_bypass']} "
            f"auto_downgrade={stats['auto_downgrade']}"]


def phase_quickstart(p: int, n: int, seed: int):
    """Order validity at the quickstart shape, default and ``xla``."""
    from repro import ParaLiNGAMConfig, fit
    from repro.core import sem

    data = make_data(p, n, seed, standardize=False)  # as the example
    out = {}
    for name in ("auto", "xla"):
        res, _ = fit(data["x"], ParaLiNGAMConfig(score_backend=name))
        out[name] = sem.is_valid_causal_order(res.order, data["b_true"])
        check(out[name], f"quickstart order under {name} is not a valid "
                         f"causal order: {res.order}")
    return [f"quickstart p={p} n={n} seed={seed}: valid causal order "
            f"auto={out['auto']} xla={out['xla']}"]


def phase_ring(p: int, n: int, seed: int, chips: int = 4):
    """The messaging ring over ``chips`` devices, dense and threshold,
    against the one-chip scan order of the same data. Yields each line as
    soon as it is known, so a run cut short still shows what finished."""
    import jax

    from repro import ParaLiNGAMConfig, fit
    from repro.core.paralingam import causal_order
    from repro.launch.mesh import make_ring_mesh

    x = make_data(p, n, seed)["x"]
    t0 = time.perf_counter()
    scan = causal_order(x, ParaLiNGAMConfig(order_backend="scan"))
    yield (f"one-chip scan reference p={p} n={n}: wall s (compile included) "
           f"{time.perf_counter() - t0:.1f}")
    mesh = make_ring_mesh(1, chips, 1)
    for thr in (False, True):
        t0 = time.perf_counter()
        with jax.set_mesh(mesh):
            res, b = fit(x, ParaLiNGAMConfig(order_backend="ring", threshold=thr))
            check(np.isfinite(np.asarray(b)).all(), "ring B is not finite")
        dt = time.perf_counter() - t0
        if not thr:
            mem = [d.memory_stats() for d in mesh.devices.flat]
            yield ("ring memory per device (bytes_in_use / peak_bytes_in_use): "
                   + ", ".join(f"{i}: {m['bytes_in_use']} / "
                               f"{m['peak_bytes_in_use']}"
                               for i, m in enumerate(mem)))
            block = 4 * x.size // chips  # one f32 row block of the data
            check(all(m["peak_bytes_in_use"] >= block for m in mem),
                  f"a device peaked below one row block ({block} bytes): "
                  "the ring did not spread its blocks")
        check(res.wire["ring"] == chips, f"ring ran on {res.wire} not {chips}")
        rule = score_gap(x, res.order, scan.order)
        yield (f"ring {'threshold' if thr else 'dense'} p={p} n={n} on "
               f"{res.wire['ring']} chips: order vs one-chip scan: {rule}; "
               f"comparisons saved vs serial {100 * res.saving_vs_serial:.2f}%; "
               f"wall s (compile included) {dt:.1f}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chip", action="store_true",
                    help="run only the messaging ring over four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    info = device_info(4 if args.four_chip else 1)
    print(f"device: platform={info['platform']} kind={info['kind']} "
          f"count={info['count']}", flush=True)
    from repro.utils.compile_cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}", flush=True)

    if args.four_chip:
        phases = [("ring", lambda: phase_ring(*FIG4_P1000, args.seed))]
    else:
        phases = [
            ("fit", lambda: phase_fit(*IJR904, args.seed)),
            ("fit_batch", lambda: phase_fit_batch(*ECOLI_CORE, args.seed)),
            ("engine", lambda: phase_engine(
                [(20, 1000), (24, 900), (40, 2000), (50, 1800)], args.seed)),
            ("quickstart", lambda: phase_quickstart(*QUICKSTART)),
        ]
    for name, run in phases:
        t0 = time.perf_counter()
        for line in run():
            print(f"[{name}] {line}", flush=True)
        print(f"[{name}] PASS in {time.perf_counter() - t0:.1f} s", flush=True)
    print(json.dumps({"ok": True, "device": info}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
