"""Query-grouped IVF-PQ scan: the LUT-in-VMEM similarity kernel.

Reference role: neighbors/detail/ivf_pq_compute_similarity-inl.cuh:271 —
per (query, probe) block, build the PQ lookup table in shared memory and
scan the list's packed codes. The TPU version rides the same pair
grouping as the IVF-Flat scan (ops/ivf_scan.py) and restates the math in
*expanded* form so the LUT depends only on the query:

    d(q, i) = ||q||² + ||c_l + dec_i||² − 2·q·c_l − 2·Σ_s q_s·cb[s, code_is]

The last term is one GEMM against a block-diagonal codebook matrix (the
per-query LUT), and the per-row sum over coded entries is a one-hot
GEMM — FLOP-rich but exactly the dense shape the MXU wants, while the
dataset stays PQ-compressed in HBM (the point of PQ: DEEP-1B-class
corpora that raw f32 cannot hold). Per row chunk the kernel transposes
the code tile, compares each subspace's code row against a sublane iota
of the book to get the one-hot OHᵀ, decodes decᵀ = CB @ OHᵀ and scores
q @ decᵀ: two GEMMs a chunk, no gather. Row norms ||c + dec||²
precompute at build like brute-force norms. The decode GEMM runs in bf16
when the caller asks for the reference's fp16-LUT mode (lut_dtype), f32
when exact, or int8 (the fp8-LUT role: per-subspace symmetric codebook
quantization, double-rate MXU int8 decode with exact int32
accumulation).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils import cdiv, round_up_to
from .ivf_scan import _INT_BIG, _QG, merge_pairs, pack_pairs, scan_window

__all__ = ["ivf_pq_scan", "make_cb_matrix", "decoded_row_norms"]


def make_cb_matrix(codebooks: jax.Array) -> jax.Array:
    """(pq_dim, book, pq_len) PER_SUBSPACE codebooks → block-structured
    (rot_dim_pad, pq_dim*book) matrix CB with SUBSPACE-MAJOR columns,
    CB[s*pq_len + l, s*book + b] = cb[s, b, l], so CB times a row's
    one-hot code vector (entry s*book + b set when its subspace-s code is
    b) is the row's decoded vector — no sub-lane reshapes or gathers
    in-kernel. The kernel builds a row chunk's one-hot transposed,
    (pq_dim*book, rows), by one broadcast compare of the transposed code
    tile, so CB @ OHᵀ decodes the whole chunk in one GEMM (see
    ``_kernel_body``)."""
    pq_dim, book, pq_len = codebooks.shape
    rot_dim = pq_dim * pq_len
    rot_pad = round_up_to(rot_dim, 128)
    # pure-jnp construction (this also runs inside jit traces when a
    # caller searches an unprepared index under jit)
    cb = jnp.zeros((rot_pad, pq_dim * book), jnp.float32)
    cbj = jnp.asarray(codebooks, jnp.float32)
    for s in range(pq_dim):
        cb = cb.at[s * pq_len : (s + 1) * pq_len,
                   s * book : (s + 1) * book].set(cbj[s].T)
    return cb


def pq_chunk_rows(pq_dim: int, book: int,
                  budget_bytes: int = 2 << 30) -> int:
    """Row-chunk bound for ops whose per-row cost is a (pq_dim, book)
    f32 plane (the per-subspace encode argmin, and the codebook gather
    that XLA lowers through a one-hot contraction on TPU): an unbounded
    pass at 500k×pq64×book256 is ~33 GB and exhausts HBM. Also capped at
    256k rows regardless of the byte budget, so small (pq_dim, book)
    planes do not admit half-million-row single-chunk programs."""
    return max(4096, min(1 << 18, budget_bytes // max(pq_dim * book * 4, 1)))


@jax.jit
def _row_norms_chunk(codes_c, labels_c, centers_rot, codebooks):
    pq_dim, book, pq_len = codebooks.shape
    c = centers_rot[labels_c]                        # (b, rot_dim)
    cs = c.reshape(c.shape[0], pq_dim, pq_len)
    # decoded vectors per subspace: (b, pq_dim, pq_len)
    dec = codebooks[jnp.arange(pq_dim)[None, :], codes_c]
    cross = 2.0 * jnp.sum(cs * dec, axis=(1, 2))
    dec2 = jnp.sum(dec * dec, axis=(1, 2))
    return jnp.sum(c * c, axis=1) + cross + dec2


def decoded_row_norms(codes, centers_rot, codebooks, list_offsets
                      ) -> jax.Array:
    """(n,) exact ||c_l(i) + decode(i)||² — subspaces are orthogonal, so
    the decode cross-terms vanish:
    = ||c||² + 2 Σ_s c_s·cb[s,code] + Σ_s ||cb[s,code]||².

    Runs in bounded row chunks (see pq_chunk_rows)."""
    codes = jnp.asarray(codes, jnp.int32)            # (n, pq_dim)
    pq_dim, book, pq_len = codebooks.shape
    n = codes.shape[0]
    sizes = np.diff(np.asarray(list_offsets))
    labels = jnp.asarray(np.repeat(np.arange(len(sizes)), sizes))
    chunk = pq_chunk_rows(pq_dim, book)
    if n <= chunk:
        return _row_norms_chunk(codes, labels, centers_rot, codebooks)
    # wrap the tail to the same chunk shape: one compiled executable
    parts = []
    for b0 in range(0, n, chunk):
        sel = jnp.asarray((np.arange(b0, b0 + chunk) % n).astype(np.int32))
        part = _row_norms_chunk(jnp.take(codes, sel, axis=0),
                                jnp.take(labels, sel, axis=0),
                                centers_rot, codebooks)
        parts.append(part[: min(chunk, n - b0)])
    return jnp.concatenate(parts)


def _kernel(offs_ref, sizes_ref, qb_ref, qn_ref, dn_ref, pen_ref, cent_ref,
            cb_ref, scl_ref, codes_ref, ov_ref, oi_ref, codes_vmem, sem,
            *, k: int, kp: int, lmax: int, pq_dim: int, book: int,
            metric: str, precision: str, has_pen: bool):
    g = pl.program_id(0)
    off = offs_ref[g]
    size = sizes_ref[g]

    # dead-group gate: see ivf_scan._kernel — the static group bound
    # leaves up to n_lists dead groups whose window DMAs are pure waste
    @pl.when(size <= 0)
    def _dead():
        ov_ref[0] = jnp.full((_QG, kp), jnp.inf, jnp.float32)
        oi_ref[0] = jnp.full((_QG, kp), -1, jnp.int32)

    @pl.when(size > 0)
    def _alive():
        _kernel_body(off, size, qb_ref, qn_ref, dn_ref, pen_ref,
                     cent_ref, cb_ref, scl_ref, codes_ref, ov_ref, oi_ref,
                     codes_vmem, sem, k=k, kp=kp, lmax=lmax, pq_dim=pq_dim,
                     book=book, metric=metric, precision=precision,
                     has_pen=has_pen)


def _kernel_body(off, size, qb_ref, qn_ref, dn_ref, pen_ref,
                 cent_ref, cb_ref, scl_ref, codes_ref, ov_ref, oi_ref,
                 codes_vmem, sem, *, k: int, kp: int, lmax: int,
                 pq_dim: int, book: int, metric: str, precision: str,
                 has_pen: bool):
    # off/size arrive as values: pl.program_id cannot be called inside a
    # pl.when branch (the CPU interpreter has no lowering for it there)
    off_al = (off // 8) * 8
    extra = off - off_al

    copy = pltpu.make_async_copy(
        codes_ref.at[pl.ds(off_al, lmax), :], codes_vmem, sem)
    copy.start()
    q = qb_ref[0]                                    # (QG, rot_pad)
    pqb = pq_dim * book
    lut_t = cb_ref.dtype        # bf16 = fp16-LUT mode; int8 = fp8-LUT role
    int8_mode = lut_t == jnp.int8
    acc_t = jnp.int32 if int8_mode else jnp.float32
    qc = jax.lax.dot_general(
        q, cent_ref[0], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision(precision))      # (QG, 1)
    copy.wait()

    # Associativity saves VMEM: q @ (CB @ OHᵀ) instead of (q @ CB) @ OHᵀ.
    # CB @ OHᵀ is exactly the chunk's *decoded rows*, transposed
    # (rot_pad, cw) — a few hundred KB — whereas the per-query LUT
    # (QG, pqb) is megabytes at large pq_dim. One-hot chunks are sized to
    # ~4 MB; at very large lmax this unrolls more GEMM pairs (compile-time
    # cost), the accepted tradeoff for a bounded VMEM footprint.
    #
    # int8 mode (role of the reference's fp8 smem LUT,
    # ivf_pq_types.hpp:110-146): CB arrives pre-quantized with
    # per-subspace symmetric scales; the one-hot is int8 too, so the
    # decode GEMM runs on the MXU's double-rate int8 path and accumulates
    # exactly in int32. The per-ROW scale column (subspaces are disjoint
    # row/column blocks of CB) rescales the decoded chunk before scoring.
    itemsize = lut_t.itemsize
    chunk = max(128, min(lmax, ((4 << 20) // (pqb * itemsize)) // 128 * 128))
    scale = -2.0 if metric == "l2" else -1.0
    # OHᵀ[s*book + b, r] = (codes[r, s] == b): each subspace's code row,
    # broadcast down the sublanes, against b = 0..book-1 (no lane-dim
    # reshape). One iota a chunk width, built once a grid step: Mosaic
    # cannot slice a ragged last chunk's out of the full one.
    b_iota = {}
    terms = []
    for c0 in range(0, lmax, chunk):
        cw = min(chunk, lmax - c0)
        ct = codes_vmem[c0 : c0 + cw, :].astype(jnp.int32).T[:pq_dim]
        if cw not in b_iota:
            b_iota[cw] = jax.lax.broadcasted_iota(jnp.int32, (1, book, cw), 1)
        oh = (ct[:, None, :] == b_iota[cw]).astype(lut_t).reshape(
            pqb, cw)                                 # (pqb, cw) OHᵀ
        decoded = jax.lax.dot_general(
            cb_ref[:], oh, (((1,), (0,)), ((), ())),
            preferred_element_type=acc_t)            # (rot_pad, cw)
        if int8_mode:
            decoded = decoded.astype(jnp.float32) * scl_ref[:]
        terms.append(scale * jax.lax.dot_general(
            q, decoded, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision(precision))) # (QG, cw)
    pq_term = jnp.concatenate(terms, axis=1) if len(terms) > 1 else terms[0]

    if metric == "l2":
        qn = qn_ref[0]                               # (QG, 1) ||q||²
        dist = jnp.maximum(qn + dn_ref[0, 0] - 2.0 * qc + pq_term, 0.0)
    else:                                            # "ip": min-order score
        dist = -qc + pq_term
    if has_pen:
        # in-kernel bitset filter as an additive penalty row (role of
        # detail/ivf_pq_search.cuh:795-797)
        dist = dist + pen_ref[0, 0]

    col = jax.lax.broadcasted_iota(jnp.int32, (_QG, lmax), 1)
    dist = jnp.where((col >= extra) & (col < extra + size), dist, jnp.inf)
    lane = jax.lax.broadcasted_iota(jnp.int32, (_QG, kp), 1)

    def extract(t, state):
        c, nv, ni = state
        best = jnp.min(c, axis=1, keepdims=True)
        pos = jnp.min(jnp.where(c <= best, col, _INT_BIG), axis=1,
                      keepdims=True)
        at = col == pos
        bid = jnp.where(jnp.isfinite(best), off_al + pos, -1)
        nv = jnp.where(lane == t, best, nv)
        ni = jnp.where(lane == t, bid, ni)
        return jnp.where(at, jnp.inf, c), nv, ni

    state = (dist, jnp.full((_QG, kp), jnp.inf, jnp.float32),
             jnp.full((_QG, kp), -1, jnp.int32))
    if k <= 16:
        for t in range(k):
            state = extract(t, state)
    else:
        state = jax.lax.fori_loop(0, k, extract, state)
    ov_ref[0] = state[1]
    oi_ref[0] = state[2]


@functools.partial(
    jax.jit,
    static_argnames=("k", "lmax", "n_groups", "pq_dim", "book", "metric",
                     "interpret", "precision", "has_pen"))
def _scan_groups(qblocks, qnorms, dn_slices, pen_slices, gcenters, cb_matrix,
                 scale_col, codes, goffs, gsizes, k, lmax, n_groups, pq_dim,
                 book, metric, interpret, precision, has_pen):
    kp = round_up_to(k, 128)
    rot_pad = qblocks.shape[2]
    kern = functools.partial(_kernel, k=k, kp=kp, lmax=lmax, pq_dim=pq_dim,
                             book=book, metric=metric, precision=precision,
                             has_pen=has_pen)
    pen_map = (lambda g, o, s: (g, 0, 0)) if has_pen else (
        lambda g, o, s: (0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_groups,),
        in_specs=[
            pl.BlockSpec((1, _QG, rot_pad), lambda g, o, s: (g, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, _QG, 1), lambda g, o, s: (g, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, lmax), lambda g, o, s: (g, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, lmax), pen_map, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, rot_pad), lambda g, o, s: (g, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),     # CB matrix (whole)
            pl.BlockSpec(memory_space=pltpu.VMEM),     # int8 row scales
            pl.BlockSpec(memory_space=pl.ANY),      # codes stay in HBM
        ],
        out_specs=[
            pl.BlockSpec((1, _QG, kp), lambda g, o, s: (g, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, _QG, kp), lambda g, o, s: (g, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        scratch_shapes=[
            pltpu.VMEM((lmax, codes.shape[1]), jnp.uint8),
            pltpu.SemaphoreType.DMA,
        ],
    )
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((n_groups, _QG, kp), jnp.float32),
            jax.ShapeDtypeStruct((n_groups, _QG, kp), jnp.int32),
        ],
        interpret=interpret,
    )(goffs, gsizes, qblocks, qnorms, dn_slices, pen_slices, gcenters,
      cb_matrix, scale_col, codes)


def ivf_pq_scan(
    codes: jax.Array,           # (n, pq_dim) u8, cluster-sorted
    row_norms2: jax.Array,      # (n,) ||c + decode||²
    centers_rot: jax.Array,     # (L, rot_dim)
    cb_matrix: jax.Array,       # (rot_pad, pq_dim*book) block-diagonal
    probed: jax.Array,          # (m, p)
    offsets: jax.Array,         # (L,)
    sizes: jax.Array,           # (L,)
    q_rot: jax.Array,           # (m, rot_dim) rotated queries
    k: int,
    lmax: int,
    pq_dim: int,
    book: int,
    metric: str = "l2",
    lut_mode: str = "bf16",     # "f32" | "bf16" | "int8"
    interpret: Optional[bool] = None,
    precision: str = "highest",
    penalty: Optional[jax.Array] = None,   # (n,) f32: +inf excludes a row
) -> Tuple[jax.Array, jax.Array]:
    """Scan probed PQ lists → per-query k best (approx values, ROW ids).
    ``penalty`` is indexed in the sorted row order of ``codes``."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    codes_p, norms_p = pad_codes_for_scan(codes, row_norms2, lmax, pq_dim)
    pen_p = None
    if penalty is not None:
        pen_p = jnp.pad(jnp.asarray(penalty, jnp.float32),
                        (0, scan_window(lmax)))
    return _ivf_pq_scan_jit(codes_p, norms_p, pen_p, centers_rot, cb_matrix,
                            probed, offsets, sizes, q_rot, k, lmax, pq_dim,
                            book, metric, lut_mode, interpret, precision)


@functools.partial(jax.jit, static_argnames=("lmax", "pq_dim"))
def pad_codes_for_scan(codes, row_norms2, lmax: int, pq_dim: int):
    """Pad codes/norms for the aligned DMA windows — a full copy of the
    compressed dataset; callers cache per index."""
    lmax_pad = scan_window(lmax)
    code_pad = round_up_to(pq_dim, 128)
    codes_p = jnp.pad(jnp.asarray(codes, jnp.uint8),
                      ((0, lmax_pad), (0, code_pad - pq_dim)))
    norms_p = jnp.pad(jnp.asarray(row_norms2, jnp.float32), (0, lmax_pad))
    return codes_p, norms_p


@functools.partial(
    jax.jit,
    static_argnames=("k", "lmax", "pq_dim", "book", "metric", "lut_mode",
                     "interpret", "precision"))
def _ivf_pq_scan_jit(codes_p, norms_p, pen_p, centers_rot, cb_matrix, probed,
                     offsets, sizes, q_rot, k, lmax, pq_dim, book, metric,
                     lut_mode, interpret, precision):
    m, p = probed.shape
    n_lists = offsets.shape[0]
    rot_dim = q_rot.shape[1]
    rot_pad = cb_matrix.shape[0]
    lmax_pad = scan_window(lmax)
    scale_col = jnp.ones((rot_pad, 1), jnp.float32)
    if lut_mode == "int8":
        # fp8-LUT role (ivf_pq_types.hpp:110-146): per-subspace symmetric
        # quantization of the block-diagonal CB. Column s*book+b and row
        # s*pq_len+l both belong to subspace s and CB is block-diagonal in
        # s, so a per-COLUMN-subspace quantize + per-ROW-subspace rescale
        # round-trips exactly (up to the int8 rounding itself).
        pq_len = rot_dim // pq_dim
        absmax = jnp.max(jnp.abs(cb_matrix).reshape(rot_pad, pq_dim, book),
                         axis=(0, 2))                    # (pq_dim,)
        scales = jnp.maximum(absmax, 1e-12) / 127.0
        cb_matrix = jnp.clip(
            jnp.round(cb_matrix.reshape(rot_pad, pq_dim, book)
                      / scales[None, :, None]), -127, 127
        ).astype(jnp.int8).reshape(rot_pad, pq_dim * book)
        scale_col = jnp.pad(jnp.repeat(scales, pq_len),
                            (0, rot_pad - rot_dim),
                            constant_values=1.0)[:, None]
    elif lut_mode == "bf16":
        # fp16-LUT mode: cast here so the kernel's operand dtypes match
        cb_matrix = cb_matrix.astype(jnp.bfloat16)
    q = jnp.pad(jnp.asarray(q_rot, jnp.float32),
                ((0, 0), (0, rot_pad - rot_dim)))
    cent_p = jnp.pad(jnp.asarray(centers_rot, jnp.float32),
                     ((0, 0), (0, rot_pad - rot_dim)))

    qtable, glist, galive, flat, order, n_groups = pack_pairs(probed,
                                                              n_lists)
    qblocks = q[qtable]                              # (G, QG, rot_pad)
    qn = jnp.sum(qblocks * qblocks, axis=2, keepdims=True)
    gcenters = cent_p[glist][:, None, :]             # (G, 1, rot_pad)
    goffs = offsets[glist]
    gsizes = jnp.where(galive, sizes[glist], 0)
    goffs_al = (goffs // 8) * 8
    dn = jax.vmap(lambda o: jax.lax.dynamic_slice(
        norms_p, (o,), (lmax_pad,)))(goffs_al)[:, None, :]
    if pen_p is None:
        pen = jnp.zeros((1, 1, lmax_pad), jnp.float32)
    else:
        pen = jax.vmap(lambda o: jax.lax.dynamic_slice(
            pen_p, (o,), (lmax_pad,)))(goffs_al)[:, None, :]

    gv, gi = _scan_groups(qblocks, qn, dn, pen, gcenters, cb_matrix,
                          scale_col, codes_p, goffs, gsizes, k, lmax_pad,
                          int(n_groups), pq_dim, book, metric, interpret,
                          precision, pen_p is not None)
    return merge_pairs(gv, gi, flat, order, m, p, k)
