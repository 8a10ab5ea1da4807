"""Device peaks: the published table, and an on-device roofline probe.

``PEAKS`` holds each chip's published peaks, keyed by JAX's
``device_kind``, with the source of every number; :func:`peaks` looks a
device up and raises for one not in the table (a default would put an
unknown chip's numbers under another chip's name). The bench's
physical-plausibility floors and roofline shares read it.

The probe measures what the chip in use actually sustains (matmul
TFLOP/s, HBM stream GB/s, random-row gather GB/s). Methodology: every
probe runs the SAME one-dispatch ``lax.fori_loop`` program at TWO
iteration counts ``(i1, i2)`` and fits the slope

    per_iter_s = (t(i2) - t(i1)) / (i2 - i1)

so every per-dispatch constant — the dispatch round trip, infeed,
program setup, clock ramp-up at the window edge — cancels exactly
instead of polluting the rate. Timing is
``autotune.measure_value_read_wall`` (content-distinct inputs; the
window closes with a host ``float()`` of a scalar folded from every
output). Loop carries feed each iteration from the previous one, so no
iteration can be elided or hoisted. The matmul slope must use iteration
counts ≥64: below that the per-iteration time itself is nonlinear (ramp
effects) and a small-iters pair over-reads.

Reference analog: the tiled brute-force design is sized against real
measured HBM (detail/knn_brute_force.cuh:61).
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from ..ops.autotune import measure_value_read_wall

__all__ = ["PEAKS", "peaks", "probe", "matmul_tflops", "hbm_stream_gbps",
           "gather_gbps", "dispatch_us", "dispatch_split"]

# published per-chip peaks, keyed by ``jax.Device.device_kind``
PEAKS: Dict[str, Dict[str, object]] = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": 'Google Cloud documentation, "TPU v5e" (system '
                  "architecture: per-chip peak compute and HBM)",
    },
}


def peaks(device=None) -> Dict[str, object]:
    """The published peaks of ``device`` (default: JAX's first device).
    Raises KeyError for a device kind not in :data:`PEAKS`."""
    dev = device or jax.devices()[0]
    kind = dev.device_kind
    if kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {kind!r} "
                       f"({dev.platform}); add it to roofline.PEAKS")
    return PEAKS[kind]


def _slope(make_fn, make_inputs, i1: int, i2: int) -> float:
    """Per-iteration seconds from a two-point fit of t(iters)."""
    times = {}
    for iters in (i1, i2):
        fn = make_fn(iters)
        ins = make_inputs(3)      # warm + 2 timed, all content-distinct
        times[iters] = measure_value_read_wall(fn, ins[1:],
                                               warm_input=ins[0])
    return (times[i2] - times[i1]) / (i2 - i1)


def matmul_tflops(n: int = 8192, dtype=jnp.bfloat16,
                  i1: int = 64, i2: int = 192) -> float:
    """Sustained TFLOP/s of chained n×n×n matmuls, slope-fitted.

    The chain c ← c @ (b/√n) keeps magnitudes stable and makes every
    matmul depend on the previous one — XLA cannot drop iterations."""
    b = jax.random.normal(jax.random.PRNGKey(1), (n, n), jnp.float32)
    bs = (b / jnp.sqrt(float(n))).astype(dtype)

    def make(iters):
        # bs rides as an ARGUMENT: closing over it would bake a 128-256 MB
        # HLO constant into the program
        @jax.jit
        def f(a, bs):
            def body(_, c):
                return jax.lax.dot_general(
                    c, bs, (((1,), (0,)), ((), ())),
                    preferred_element_type=dtype)
            return jax.lax.fori_loop(0, iters, body, a)
        return lambda a: f(a, bs)

    def inputs(m):
        return [jax.random.normal(jax.random.PRNGKey(20 + j), (n, n),
                                  jnp.float32).astype(dtype)
                for j in range(m)]

    return 2.0 * n ** 3 / _slope(make, inputs, i1, i2) / 1e12


def hbm_stream_gbps(mbytes: int = 1024, i1: int = 64, i2: int = 256
                    ) -> float:
    """Sustained HBM GB/s on a chained read+write f32 stream.

    Each iteration rescales the full buffer with an iteration-dependent
    factor large enough to change every f32 value (not elidable)."""
    rows = (mbytes << 20) // 4 // 1024
    traffic = 2.0 * 4 * rows * 1024      # read + write per iteration

    def make(iters):
        @jax.jit
        def f(x):
            def body(i, c):
                s = 1.0 + (2.0 ** -6) * (i % 3 + 1).astype(jnp.float32)
                return c * s
            return jax.lax.fori_loop(0, iters, body, x)
        return f

    def inputs(m):
        return [jax.random.normal(jax.random.PRNGKey(10 + j),
                                  (rows, 1024), jnp.float32)
                for j in range(m)]

    return traffic / _slope(make, inputs, i1, i2) / 1e9


def gather_gbps(tbl_rows: int = 1 << 20, row_d: int = 128,
                g_rows: int = 1 << 18, i1: int = 16, i2: int = 64
                ) -> float:
    """Effective GB/s of iteration-dependent random-row gathers (the
    traffic shape of CAGRA hops and IVF-PQ refine)."""
    tbl = jax.random.normal(jax.random.PRNGKey(3), (tbl_rows, row_d),
                            jnp.float32)

    def make(iters):
        @jax.jit
        def f(x, t):
            def body(i, c):
                # the carry folds into the index base so the gather chain
                # is INPUT-dependent — an index stream derived from the
                # loop counter alone is value-identical across calls and
                # a replaying backend could serve it from cache
                iu = i.astype(jnp.uint32) + c[0].astype(jnp.uint32)
                base = iu * jnp.uint32(1315423911) + jnp.uint32(2654435761)
                idx = (base + jnp.arange(g_rows, dtype=jnp.uint32)
                       * jnp.uint32(2654435761)) % jnp.uint32(tbl_rows)
                g = jnp.take(t, idx.astype(jnp.int32), axis=0)
                return c + g.sum(axis=0)
            return jax.lax.fori_loop(0, iters, body, x)

        return lambda x: f(x, tbl)

    def inputs(m):
        return [jnp.zeros((row_d,), jnp.float32) + j for j in range(m)]

    return g_rows * row_d * 4 / _slope(make, inputs, i1, i2) / 1e9


def dispatch_us(reps: int = 11) -> float:
    """Median round-trip of a trivial dispatch (1-element add + sync).

    Deliberately NOT amortized: this is the per-call constant the slope
    probes cancel, reported so readers can judge how much of any
    per-call latency is transport."""
    from ..ops.autotune import measure as _median_time

    x = jnp.zeros((8, 128), jnp.float32)

    @jax.jit
    def f(x):
        return x + 1.0

    return _median_time(f, x, reps=reps) * 1e6


def dispatch_split(reps: int = 32) -> dict:
    """The ISSUE 12 decomposition of the dispatch constant: first-call
    vs amortized.

    ``dispatch_once_us`` is the round trip of the FIRST post-compile
    dispatch of a fresh executable (program upload + the full
    dispatch+sync transport) — what an un-warmed serving bucket or a
    per-hop kernel-launch loop pays. ``dispatch_steady_us`` is the
    amortized per-dispatch cost of ``reps`` back-to-back asynchronous
    dispatches closed by ONE sync — what a pipelined (double-buffered)
    serving loop or the one-dispatch megakernel actually pays per call.
    The gap between the two is the attribution the megakernel's win
    needs: a big once/steady ratio says the fixed per-launch cost, not
    the kernel math, bounded the old per-hop path."""
    import time as _time

    x = jnp.zeros((8, 128), jnp.float32)

    def f(x):
        return x + 1.0

    # fresh executable per probe run (a lambda is a distinct jit cache
    # key per call of dispatch_split, so re-probes stay honest)
    g = jax.jit(lambda a: f(a) * 1.0)
    compiled = g.lower(x).compile()
    t0 = _time.perf_counter()
    jax.block_until_ready(compiled(x))
    once = _time.perf_counter() - t0
    # steady: back-to-back async dispatches, one closing sync; each
    # call feeds the next so the chain cannot be collapsed
    y = x
    t0 = _time.perf_counter()
    for _ in range(reps):
        y = compiled(y)
    jax.block_until_ready(y)
    steady = (_time.perf_counter() - t0) / reps
    return {"dispatch_once_us": round(once * 1e6, 1),
            "dispatch_steady_us": round(steady * 1e6, 1)}


def probe(quick: bool = False) -> Dict[str, float]:
    """Measure this device's effective peaks via slope fits. ~8 compiles;
    each probe streams seconds of device work so the fit is stable.

    ``quick`` trims the large-iters points (shorter windows, same
    method); the matmul pair stays ≥64 — see the module docstring."""
    mm = (64, 128) if quick else (64, 192)
    st = (64, 160) if quick else (64, 256)
    ga = (16, 48) if quick else (16, 64)
    return {
        "matmul_bf16_tflops": round(matmul_tflops(dtype=jnp.bfloat16,
                                                  i1=mm[0], i2=mm[1]), 1),
        "matmul_f32_tflops": round(matmul_tflops(dtype=jnp.float32,
                                                 i1=mm[0], i2=mm[1]), 1),
        "hbm_stream_gbps": round(hbm_stream_gbps(
            mbytes=512 if quick else 1024, i1=st[0], i2=st[1]), 1),
        "gather_gbps": round(gather_gbps(i1=ga[0], i2=ga[1]), 1),
        "dispatch_us": round(dispatch_us(), 1),
        # first-call vs amortized split (ISSUE 12): attributes how much
        # of dispatch_us is per-launch fixed cost a pipelined/one-shot
        # dispatch path amortizes away
        **dispatch_split(),
    }


if __name__ == "__main__":
    import json

    print(json.dumps(probe()))
