"""Measure the TPU's vector (VPU) and transcendental (EUP) ceilings with
Pallas microkernels, for ``peaks.json``.

    python3 chipbench/peaks_probe.py            # on a TPU; prints one JSON line

Neither ceiling is published for a TPU v5e, and the score kernels are bound
by them rather than by the matrix unit. Each probe keeps one (rows, 512)
float32 block in vector registers and applies one operation to every element
``iters`` times in a dependent loop, so nothing but that operation's
throughput limits it:

  * ``vpu_f32_ops_per_s``: ``v = v * a + b``, counted as two operations;
  * ``exp_per_s``: ``v = exp(-v)`` (a fixed point near 0.567);
  * ``log1p_per_s``: ``v = log1p(v)`` (decays towards 0, never non-finite).

The rate is elements x iterations x operations over the time between a long
and a short call of the same kernel, so launch overhead cancels. Each probe
runs ``reps`` times and the fastest pair counts: a ceiling is what the unit
can do, not what it did on average. Exits non-zero off a TPU.
"""

from __future__ import annotations

import functools
import json
import sys
import time

import jax
import jax.numpy as jnp

ROWS = 64
LANES = 512
#: name: (operation, operations counted per application, starting value)
OPS = {
    "vpu_f32_ops_per_s": (lambda v: v * 0.999 + 0.001, 2, 1.0),
    "exp_per_s": (lambda v: jnp.exp(-v), 1, 0.5),
    "log1p_per_s": (jnp.log1p, 1, 1.0),
}


#: Operations applied per loop step (the kernel compiler unrolls no loop).
UNROLL = 16


def _kernel(op, iters, x_ref, o_ref):
    def step(_, v):
        for _ in range(UNROLL):
            v = op(v)
        return v

    o_ref[...] = jax.lax.fori_loop(0, iters // UNROLL, step, x_ref[...])


@functools.lru_cache(maxsize=None)
def _call(name: str, iters: int):
    from jax.experimental import pallas as pl

    op = OPS[name][0]
    return jax.jit(pl.pallas_call(
        functools.partial(_kernel, op, iters),
        out_shape=jax.ShapeDtypeStruct((ROWS, LANES), jnp.float32)))


def _seconds(name: str, iters: int, x) -> float:
    f = _call(name, iters)
    f(x).block_until_ready()  # compile and warm
    t0 = time.perf_counter()
    out = f(x).block_until_ready()
    dt = time.perf_counter() - t0
    if not bool(jnp.isfinite(out).all()):
        raise RuntimeError(f"{name} probe produced non-finite values")
    return dt


def measure(long_iters: int = 1 << 22, short_iters: int = 1 << 12,
            reps: int = 3) -> dict:
    out = {}
    for name, (_, ops, start) in OPS.items():
        x = jnp.full((ROWS, LANES), start, jnp.float32)
        best = min(_seconds(name, long_iters, x) - _seconds(name, short_iters, x)
                   for _ in range(reps))
        out[name] = ROWS * LANES * (long_iters - short_iters) * ops / best
    out["device_kind"] = jax.devices()[0].device_kind
    return out


def main() -> int:
    if jax.devices()[0].platform != "tpu":
        print(f"no TPU: JAX found {jax.devices()[0].platform}", file=sys.stderr)
        return 2
    print(json.dumps(measure()))
    return 0


if __name__ == "__main__":
    sys.exit(main())
