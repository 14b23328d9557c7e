"""Async LiNGAM serving engine: continuous batching for multi-tenant
causal-discovery traffic.

``LingamEngine`` (the sync front door) is submit-then-synchronous-``flush``:
one caller, one thread, dispatches block the queue. ``AsyncLingamEngine``
puts the same pack -> ``fit_batch`` -> unpad bucket dispatch
(``lingam_engine.dispatch_bucket``) behind the continuous-batching core
(``serve.batching``): any number of submitter threads enqueue concurrently
and immediately get a ``Ticket``; dispatcher threads flush each pow-2
``(p, n)`` bucket when it fills (``max_batch``) or when its oldest request
has waited ``flush_interval`` — the occupancy-vs-latency knob — with
per-request deadlines/priorities, bounded-queue backpressure (block or
shed), bounded failed-dispatch retry, per-bucket circuit breakers, and a
stats surface (queue depth, batch occupancy, padding waste, shed/retry/
quarantine counters, per-bucket p50/p95 latency). See ``serve/batching.py``
for the request lifecycle diagram and the delivery guarantees (an admitted
request is never silently dropped).

Fault-tolerance layers (PR 7):

* ``replicas > 1`` (or an explicit ``pool_cfg``) drains the one admission
  queue with a **replicated dispatcher pool** (``serve/replica.py``): per-
  replica health states, a hung-dispatch watchdog with a hard wall-clock
  budget, and failover re-queue — a crashed or wedged replica's batch moves
  to a healthy peer instead of stranding its callers.
* ``prewarm=[(p, n), ...]`` **AOT-compiles** the listed bucket shapes at
  construction (``paralingam.aot_fit_batch``) and dispatches through the
  stored executables, so a fresh bucket's first request pays no cold-start
  compile (which otherwise reads as a latency spike — or, under breakers
  and deadlines, as a sick bucket).
* ``serve_cfg.validate`` (default on) runs the ``core.validate`` admission
  guardrails at ``submit``: NaN/Inf cells, constant/duplicate variables and
  p > n rank deficiency are rejected with a typed ``DatasetError`` before
  any queueing or device work (counted in ``stats()["invalid_datasets"]``).

Determinism contract: a request served here returns *bit-identical* causal
orders to a dedicated ``fit`` call — batching, padding, arrival order,
replica failover and pre-warmed executables change only latency, never
results (asserted under randomized multi-threaded request storms and
seeded chaos schedules in tests/test_async_engine.py /
tests/test_replica.py / tests/test_serve_storm.py).

Everything timing- or failure-related is injectable: ``clock`` (a
``utils.clock.Clock``) and ``dispatch`` (the bucket-level device call — one
callable shared by all replicas, or a list of one per replica) seam the
engine for deterministic fake-clock and fault-injection tests — and for
``start=False`` + ``step()``/``run_once()`` manual pumping with zero
threads involved.
"""

from __future__ import annotations

import threading

import numpy as np

from repro.core.paralingam import DISPATCH_COUNTERS, aot_fit_batch
from repro.core.paralingam import ParaLiNGAMConfig
from repro.serve.batching import (
    BatchingConfig,
    BatchingCore,
    DispatchFailed,
    Ticket,
)
from repro.serve.lingam_engine import (
    LingamFit,
    LingamServeConfig,
    bucket_shape,
    check_dataset,
    check_engine_config,
    dispatch_bucket,
)
from repro.serve.replica import ReplicaPool, ReplicaPoolConfig
from repro.utils import tracing
from repro.utils.shapes import next_pow2


class AsyncLingamEngine:
    """Thread-safe continuously-batching LiNGAM front door.

    ``submit`` returns a :class:`~repro.serve.batching.Ticket` whose
    ``result()`` blocks for the request's :class:`LingamFit` (or raises its
    typed ``ServeError``); ``fit``/``fit_many`` are the blocking
    conveniences. Close with ``close()`` (or use as a context manager) to
    drain and stop the dispatcher thread(s).

    ``dispatch`` (signature ``dispatch(bucket, payloads) -> list[LingamFit]``)
    defaults to the real device path and is the fault-injection seam; pass a
    list of callables for per-replica seams. ``start=False`` skips the
    background threads so tests pump the engine manually via ``step()`` (or
    ``pool.run_once()`` with replicas) under a ``FakeClock``.
    """

    def __init__(self, config: ParaLiNGAMConfig | None = None,
                 serve_cfg: LingamServeConfig | None = None, rules=None, *,
                 batch_cfg: BatchingConfig | None = None, clock=None,
                 dispatch=None, start: bool = True,
                 replicas: int = 1, pool_cfg: ReplicaPoolConfig | None = None,
                 prewarm=None):
        self.config = check_engine_config(config)
        self.serve_cfg = serve_cfg or LingamServeConfig()
        self.rules = rules
        batch_cfg = batch_cfg or BatchingConfig(
            max_batch=self.serve_cfg.max_batch)
        if batch_cfg.max_batch > self.serve_cfg.max_batch:
            raise ValueError(
                f"batch_cfg.max_batch={batch_cfg.max_batch} exceeds "
                f"serve_cfg.max_batch={self.serve_cfg.max_batch} (the "
                "dispatch-side batch bound)")
        self._compiled: dict = {}  # (b_pad, p_pad, n_pad) -> CompiledFitBatch
        self.prewarm_stats = {"buckets": 0, "executables": 0,
                              "compile_seconds": 0.0}
        self._invalid = 0
        self._inv_mu = threading.Lock()
        if prewarm:
            self.prewarm(prewarm)

        seams = dispatch if isinstance(dispatch, (list, tuple)) else None
        if seams is not None:
            if pool_cfg is None:
                pool_cfg = ReplicaPoolConfig(replicas=len(seams))
            elif pool_cfg.replicas != len(seams):
                raise ValueError(
                    f"{len(seams)} dispatch seams for "
                    f"{pool_cfg.replicas} replicas")
            first = seams[0]
        else:
            first = dispatch or self._device_dispatch
        self._dispatch_seam = first
        self.core = BatchingCore(self._dispatch_checked, batch_cfg,
                                 clock=clock, name="lingam-async")
        self.pool: ReplicaPool | None = None
        if replicas > 1 or pool_cfg is not None or seams is not None:
            pcfg = pool_cfg or ReplicaPoolConfig(replicas=replicas)
            checked = None
            if seams is not None:
                checked = [self._make_checked(s) for s in seams]
            self.pool = ReplicaPool(self.core, pcfg, checked, start=start)
        elif start:
            self.core.start()

    # -- AOT pre-warm -------------------------------------------------------

    def prewarm(self, shapes) -> dict:
        """AOT-compile the bucket executables the given request ``(p, n)``
        shapes will land on — every pow-2 batch count up to ``max_batch``
        when batch-count padding is on (partial flushes hit too), else just
        the full batch. Dispatches route through the stored
        ``jax.stages.Compiled`` objects directly: ``lower().compile()``
        alone would NOT warm the jit call path (the jit dispatch cache is
        separate — measured in benchmarks/bench_serve.py). Returns
        ``prewarm_stats``."""
        scfg = self.serve_cfg
        buckets = sorted({bucket_shape(p, n, scfg) for p, n in shapes})
        if scfg.pad_batch_pow2:
            batch_sizes = []
            b = 1
            while b < scfg.max_batch:
                batch_sizes.append(b)
                b *= 2
            batch_sizes.append(scfg.max_batch)
        else:
            batch_sizes = [scfg.max_batch]
        for p_pad, n_pad in buckets:
            for b_pad in batch_sizes:
                key = (b_pad, p_pad, n_pad)
                if key in self._compiled:
                    continue
                exe = aot_fit_batch(b_pad, p_pad, n_pad, self.config,
                                    padded=True, rules=self.rules)
                self._compiled[key] = exe
                self.prewarm_stats["executables"] += 1
                self.prewarm_stats["compile_seconds"] += exe.compile_seconds
        self.prewarm_stats["buckets"] = len(buckets)
        return dict(self.prewarm_stats)

    # -- dispatch seam ------------------------------------------------------

    def _device_dispatch(self, bucket, payloads) -> list[LingamFit]:
        """Default dispatch: the shared pack -> fit_batch -> unpad path
        (through the AOT executable cache when pre-warmed)."""
        p_pad, n_pad = bucket
        return dispatch_bucket(payloads, p_pad, n_pad, self.config,
                               self.serve_cfg, self.rules,
                               compiled=self._compiled)

    def _dispatch_checked(self, bucket, payloads):
        return self._checked(self._dispatch_seam, bucket, payloads)

    def _make_checked(self, seam):
        return lambda bucket, payloads: self._checked(seam, bucket, payloads)

    def _checked(self, seam, bucket, payloads):
        """Run the (injectable) dispatch seam, then validate each result:
        non-finite fits — a NaN'd Cholesky, a poisoned batch neighbour — are
        converted to per-request ``DispatchFailed`` rejections so the core
        retries or fails *that* request instead of delivering corrupt output.
        Also accounts the bucket's padding waste (pow-2 shape + batch-count
        padding cells vs live data cells)."""
        p_pad, n_pad = bucket
        results = seam(bucket, payloads)
        if results is not None and len(results) == len(payloads):
            live = sum(int(np.prod(x.shape)) for x in payloads)
            b_pad = len(payloads)
            if self.serve_cfg.pad_batch_pow2:
                b_pad = min(next_pow2(len(payloads)), self.serve_cfg.max_batch)
            total = b_pad * p_pad * n_pad
            self.core.note_bucket(bucket, pad_cells=total - live,
                                  total_cells=total)
            results = [
                r if isinstance(r, BaseException) or _fit_finite(r)
                else DispatchFailed(
                    f"non-finite fit result for request in bucket {bucket}")
                for r in results
            ]
        return results

    # -- intake -------------------------------------------------------------

    def submit(self, x, *, priority: int = 0, deadline: float | None = None,
               overflow: str | None = None) -> Ticket:
        """Enqueue one (p, n) dataset. ``deadline`` is relative seconds on
        the engine clock: the bucket flushes early enough to honor it, and a
        request still queued past it is failed with ``RequestTimeout``
        (work already on the device is delivered, not cancelled). Higher
        ``priority`` wins within a bucket. ``overflow`` ("block"/"shed")
        overrides the configured backpressure policy for this request.
        With ``serve_cfg.validate`` a degenerate dataset raises a typed
        ``DatasetError`` here, before any queueing."""
        try:
            x = check_dataset(x, validate=self.serve_cfg.validate)
        except ValueError:
            with self._inv_mu:
                self._invalid += 1
            raise
        bucket = bucket_shape(*x.shape, self.serve_cfg)
        return self.core.submit(x, bucket, priority=priority,
                                deadline=deadline, overflow=overflow)

    def fit(self, x, *, priority: int = 0, deadline: float | None = None,
            timeout: float | None = None) -> LingamFit:
        """Blocking submit + result."""
        return self.submit(x, priority=priority, deadline=deadline).result(timeout)

    def fit_many(self, xs, *, timeout: float | None = None) -> list[LingamFit]:
        tickets = [self.submit(x) for x in xs]
        return [t.result(timeout) for t in tickets]

    # -- control / observability -------------------------------------------

    def step(self) -> int:
        """Manual scheduling pass (``start=False`` engines / tests). Returns
        the number of batches dispatched. With a replica pool, prefer
        ``pool.run_once()`` so replica health is exercised too."""
        return self.core.step()

    def join(self, timeout: float | None = None) -> bool:
        return self.core.join(timeout)

    @property
    def pending(self) -> int:
        return self.core.pending

    def stats(self) -> dict:
        """Core stats snapshot plus the estimator-level counters threaded up
        from ``core.paralingam``, the admission guardrail rejections,
        pre-warm totals, and — with a replica pool — per-replica health and
        watchdog counters.

        ``kernel_bypass`` is the requested-kernel-but-ran-jnp tripwire: since
        the moments kernel redesign every backend serves the padded batched
        route, so it must read 0 (asserted by the engine suites).
        ``auto_downgrade`` counts dispatches where ``score_backend="auto"``
        resolved to a jnp formulation — the off-accelerator platform policy
        report that replaced the old bypass RuntimeWarning.
        ``compiles``, ``compile_cache_hits`` and ``handoff_bytes`` are the
        process-wide ``utils.tracing`` counters: executables built or loaded
        from the compile cache, the loads alone, and bytes of host data
        handed to the device."""
        out = self.core.snapshot()
        out.update(tracing.counters(*DISPATCH_COUNTERS, "compiles",
                                    "compile_cache_hits", "handoff_bytes"))
        with self._inv_mu:
            out["invalid_datasets"] = self._invalid
        out["prewarm"] = dict(self.prewarm_stats)
        if self.pool is not None:
            out["pool"] = self.pool.snapshot()
        return out

    def close(self, *, drain: bool = True, timeout: float | None = None) -> None:
        if self.pool is not None:
            self.pool.close(drain=drain, timeout=timeout)
        else:
            self.core.close(drain=drain, timeout=timeout)

    def __enter__(self) -> "AsyncLingamEngine":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


def _fit_finite(f: LingamFit) -> bool:
    return bool(np.isfinite(f.b).all() and np.isfinite(f.noise_var).all())
