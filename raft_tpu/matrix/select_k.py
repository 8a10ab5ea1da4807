"""Batched top-k selection: analog of ``raft::matrix::select_k``.

Reference: raft/matrix/detail/select_radix.cuh (radix "AIR top-k") and
select_warpsort.cuh (bitonic warp queues), with a heuristic auto-choice
(select_k-inl.cuh:48-72). Used by brute force, IVF-Flat, IVF-PQ and CAGRA.

TPU design, two engines (mirroring the reference's two families):

* ``TOPK`` — XLA's ``lax.top_k`` partial sort. Near-free on narrow rows
  (n ≲ 256) but its cost grows super-linearly with row length: ~3 ms at
  (10k, 1024, k=20) and ~9 ms at (10k, 8192, k=10) on the measured chip.
* ``KPASS`` — a Pallas kernel running the flat-scan's k-pass min-extract
  over 128-row blocks (the warpsort-queue role): k vectorized
  min+invalidate sweeps per row block, entirely in VMEM. Slope-measured
  ~6x faster than TOPK at (10k, 1024, k=20) (0.5 vs 3.0 ms) and ~4x at
  (10k, 8192, k=10) (scratch/exp_select_slope_r5.json, r5). Exact, same
  tie-breaking as top_k (lowest index first).

``RADIX`` remains an alias: the radix/AIR histogram engine does not
transfer to TPU (histograms lower to serialized scatters or FLOP-heavy
one-hot contractions). ``AUTO`` picks KPASS on TPU for f32 rows with
k ≤ 64 and 512 ≤ n ≤ 4096, TOPK otherwise. The column cap is a VMEM
bound, not a tuning choice: the kernel keeps ~5 live (128, n) f32/i32
planes on the scoped-VMEM stack, and measured compile-time OOMs on v5e
put (128, 15744) at 24.8 MB and even (128, 8192) at 21.3 MB inside a
larger program against the 16 MB scoped limit — 4096 (~10.5 MB) is the
rehearsed-safe width. Callers with wider rows chunk first
(brute_force._wide_select_k).
"""
from __future__ import annotations

import enum
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..core.errors import expects
from ..core import interop, tracing

__all__ = ["SelectAlgo", "select_k", "tune_select_k"]

_INT_BIG = 2 ** 30


class SelectAlgo(enum.Enum):
    """Mirror of raft/matrix/select_k_types.hpp:36.

    ``KPASS`` is this library's warpsort-queue analog (see module
    docstring); ``RADIX`` stays an alias of TOPK so reference callers
    porting ``select_k(..., SelectAlgo::kRadix...)`` keep working.
    """

    AUTO = "auto"
    TOPK = "topk"        # direct lax.top_k
    KPASS = "kpass"      # Pallas k-pass min-extract (warpsort role)
    RADIX = "radix"      # alias of TOPK on TPU (no histogram engine)


def _topk_smallest(values: jax.Array, k: int, select_min: bool):
    v = -values if select_min else values
    vals, idxs = jax.lax.top_k(v, k)
    return (-vals if select_min else vals), idxs


# --------------------------------------------------------------------------
# KPASS engine
# --------------------------------------------------------------------------

def _kpass_kernel(x_ref, ov_ref, oi_ref, *, k: int, kp: int, n: int,
                  n_real: int):
    """k passes of (row-min, invalidate) over a (128, n) VMEM block.

    Tie-break matches lax.top_k: among equal values the lowest column
    wins. An explicit alive MASK (not +inf overwrites) tracks extracted
    cells — +inf is a legal input value (filter penalties, pad columns)
    and overwriting with it would re-extract column 0 forever once an
    inf enters the top-k. ``n_real`` confines selection to genuine
    columns so +inf PADDING can never be returned as an index."""
    x = x_ref[0]
    col = jax.lax.broadcasted_iota(jnp.int32, (128, n), 1)
    lane = jax.lax.broadcasted_iota(jnp.int32, (128, kp), 1)
    # the alive mask is carried as int32: Mosaic cannot legalize an
    # scf.for (the k > 32 fori_loop) that carries an i1 vector
    alive0 = (col < n_real).astype(jnp.int32)

    def extract(t, state):
        alive, nv, ni = state
        live = alive != 0
        masked = jnp.where(live, x, jnp.inf)
        best = jnp.min(masked, axis=1, keepdims=True)
        pos = jnp.min(jnp.where(live & (masked <= best), col, _INT_BIG),
                      axis=1, keepdims=True)
        at = col == pos
        nv = jnp.where(lane == t, best, nv)
        ni = jnp.where(lane == t, pos, ni)
        return jnp.where(at, 0, alive), nv, ni

    state = (alive0, jnp.full((128, kp), jnp.inf, jnp.float32),
             jnp.full((128, kp), -1, jnp.int32))
    if k <= 32:
        for t in range(k):
            state = extract(t, state)
    else:
        state = jax.lax.fori_loop(0, k, extract, state)
    ov_ref[0] = state[1]
    oi_ref[0] = state[2]


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def _kpass_2d(values: jax.Array, k: int, interpret: bool):
    """(m, n) f32 → k smallest per row via the Pallas kernel.

    Rows pad to a 128 multiple (dropped after), columns to a 128
    multiple with +inf."""
    from jax.experimental import pallas as pl

    from ..utils import round_up_to

    m, n = values.shape
    mp = round_up_to(m, 128)
    np_ = round_up_to(n, 128)
    kp = round_up_to(k, 128)
    x = jnp.pad(values.astype(jnp.float32),
                ((0, mp - m), (0, np_ - n)),
                constant_values=jnp.inf)
    mb = mp // 128
    call = pl.pallas_call(
        functools.partial(_kpass_kernel, k=k, kp=kp, n=np_, n_real=n),
        grid=(mb,),
        in_specs=[pl.BlockSpec((1, 128, np_), lambda i: (i, 0, 0))],
        out_specs=[pl.BlockSpec((1, 128, kp), lambda i: (i, 0, 0)),
                   pl.BlockSpec((1, 128, kp), lambda i: (i, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((mb, 128, kp), jnp.float32),
                   jax.ShapeDtypeStruct((mb, 128, kp), jnp.int32)],
        interpret=interpret,
    )
    v, i = call(x.reshape(mb, 128, np_))
    return (v[:, :, :k].reshape(mp, k)[:m],
            i[:, :, :k].reshape(mp, k)[:m])


def _kpass_smallest(values: jax.Array, k: int, select_min: bool):
    interpret = jax.default_backend() != "tpu"
    v2 = values if select_min else -values
    lead = values.shape[:-1]
    flat = v2.reshape(-1, values.shape[-1])
    vals, idxs = _kpass_2d(flat, k, interpret)
    vals = vals.reshape(*lead, k)
    idxs = idxs.reshape(*lead, k)
    if not select_min:
        vals = -vals
    # match TOPK's dtype contract: values come back in the input dtype
    # (the kernel computes in f32)
    return vals.astype(values.dtype), idxs


def _kpass_safe(values: jax.Array, k: int) -> bool:
    """Shapes the kernel can COMPILE and run sanely: the scoped-VMEM
    column cap, a supported dtype, and a real TPU backend (interpret
    mode exists for unit tests only — dispatching it on hot paths is a
    correctness-of-performance bug)."""
    n = values.shape[-1]
    return (n <= 4096 and jax.default_backend() == "tpu"
            and values.dtype in (jnp.float32, jnp.bfloat16, jnp.float16))


def _kpass_eligible(values: jax.Array, k: int) -> bool:
    """Safety bounds plus the measured-win heuristic window (used when
    no tuning cache entry exists)."""
    rows = 1
    for s in values.shape[:-1]:
        rows *= s
    return (_kpass_safe(values, k) and k <= 64 and values.shape[-1] >= 512
            and rows >= 512)


def tune_select_k(rows: int, n: int, k: int, select_min: bool = True,
                  reps: int = 5):
    """Measure both engines for this shape class on-device and cache the
    winner (the measurement role of the reference's
    ``choose_select_k_algorithm`` table, select_k-inl.cuh:48-72). Call
    eagerly, not under jit."""
    from ..ops import autotune

    x = jax.random.normal(jax.random.PRNGKey(0), (rows, n), jnp.float32)
    key = autotune.shape_bucket("select_k", n=n, k=k)
    cands = {
        "topk": jax.jit(lambda v: _topk_smallest(v, k, select_min)),
    }
    if _kpass_safe(x, k):
        # shapes past the VMEM column cap must not even be measured
        # (compile-time OOM), and off-TPU the kernel only exists in
        # interpret mode — nothing real to measure
        cands["kpass"] = jax.jit(lambda v: _kpass_smallest(v, k, select_min))
    return autotune.tune_best(key, cands, x, reps=reps, force=True)


@interop.auto_convert_output
@tracing.annotate("raft_tpu::matrix::select_k")
def select_k(
    values: jax.Array,
    k: int,
    select_min: bool = True,
    indices: Optional[jax.Array] = None,
    algo: SelectAlgo | str = SelectAlgo.AUTO,
) -> Tuple[jax.Array, jax.Array]:
    """Per-row k smallest (or largest) of ``values`` (..., n).

    Returns (values (..., k), indices i32 (..., k)), sorted best-first.
    ``indices`` optionally maps positions to global ids (the reference's
    in-idx pass-through used when selecting across tiles).
    """
    algo = SelectAlgo(algo) if not isinstance(algo, SelectAlgo) else algo
    n = values.shape[-1]
    expects(0 < k <= n, "k=%d out of range for row length %d", k, n)
    if algo is SelectAlgo.AUTO:
        # measured winner first (tune_select_k's cache), static
        # eligibility heuristic otherwise
        from ..ops import autotune

        hit = autotune.lookup(autotune.shape_bucket("select_k", n=n, k=k))
        if hit == "kpass" and _kpass_safe(values, k):
            # a measured win needs only the safety bounds, not the
            # untuned heuristic window — the tuner's verdict is honored
            # for every shape it could actually have measured
            algo = SelectAlgo.KPASS
        elif hit == "topk":
            algo = SelectAlgo.TOPK
        else:
            algo = (SelectAlgo.KPASS if _kpass_eligible(values, k)
                    else SelectAlgo.TOPK)
    if algo is SelectAlgo.KPASS:
        # guarded: a KPASS compile/execution failure (unrehearsed shape,
        # new chip generation) demotes to the exact TOPK engine instead
        # of failing the call (ops/guarded.py)
        from ..ops.guarded import guarded_call

        vals, idxs = guarded_call(
            "select_k.kpass",
            lambda: _kpass_smallest(values, k, select_min),
            lambda: _topk_smallest(values, k, select_min))
    else:
        vals, idxs = _topk_smallest(values, k, select_min)
    if indices is not None:
        idxs = jnp.take_along_axis(indices, idxs, axis=-1)
    return vals, idxs.astype(jnp.int32) if idxs.dtype != jnp.int32 else idxs
