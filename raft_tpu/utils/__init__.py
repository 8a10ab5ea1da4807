"""Small integer/shape utilities shared across raft_tpu.

TPU analog of the reference's ``raft/util/`` helpers (pow2_utils.cuh,
integer_utils.hpp): alignment and tiling arithmetic used to size Pallas
blocks and padded layouts.
"""
from __future__ import annotations

import math
import os

__all__ = [
    "cdiv",
    "env_float",
    "hdot",
    "round_up_to",
    "round_down_to",
    "next_pow2",
    "is_pow2",
    "pad_to",
    "run_query_chunks",
    "shard_map_compat",
    "use_compile_cache",
    "LANES",
    "SUBLANES_F32",
    "SUBLANES_BF16",
]

# TPU register tiling: last dim is always 128 lanes; sublane count depends on
# dtype (8 for f32, 16 for bf16, 32 for int8).
LANES = 128
SUBLANES_F32 = 8
SUBLANES_BF16 = 16


def cdiv(a: int, b: int) -> int:
    """Ceiling division."""
    return -(-a // b)


def env_float(name: str, default: float) -> float:
    """Float env knob with a silent fall-back to ``default`` on unset or
    unparseable values (operator knobs must never crash a serving
    process over a typo)."""
    import os

    raw = os.environ.get(name, "")
    try:
        return float(raw) if raw else default
    except ValueError:
        return default


def env_int(name: str, default: int) -> int:
    """Integer twin of :func:`env_float` — same never-crash contract."""
    import os

    raw = os.environ.get(name, "")
    try:
        return int(raw) if raw else default
    except ValueError:
        return default


def round_up_to(x: int, m: int) -> int:
    """Round ``x`` up to the nearest multiple of ``m``."""
    return cdiv(x, m) * m


def round_down_to(x: int, m: int) -> int:
    """Round ``x`` down to the nearest multiple of ``m``."""
    return (x // m) * m


def next_pow2(x: int) -> int:
    """Smallest power of two >= x (x >= 1)."""
    if x <= 1:
        return 1
    return 1 << (x - 1).bit_length()


def is_pow2(x: int) -> bool:
    return x > 0 and (x & (x - 1)) == 0


def pad_to(x: int, m: int) -> int:
    """Amount of padding needed to reach the next multiple of ``m``."""
    return round_up_to(x, m) - x


def log2i(x: int) -> int:
    """Integer log2 of a power of two."""
    return int(math.log2(x))


def hdot(x, y):
    """f32-accurate matmul (MXU 3-pass; JAX's default precision does
    single-pass bf16 multiplies, ~1e-3 relative distance error — enough to
    mis-rank near-ties in exact kNN). Matches the reference's fp32 cuBLAS
    GEMMs (linalg/gemm.cuh)."""
    import jax.numpy as jnp

    return jnp.matmul(x, y, precision="highest")


def run_query_chunks(fn, q, chunk: int, res=None):
    """THE chunked-search loop: apply ``fn((m_c, d) chunk, start_row)``
    over row-chunks of ``q`` and concatenate the (vals, ids) pairs.

    ``res`` (a Resources or bare Deadline, optional) adds a
    cancellation + deadline checkpoint between chunk dispatches;
    ``DeadlineExceeded`` carries the completed chunks' partial results.
    Every chunked search entry point and guarded XLA fallback routes
    through this one audited implementation."""
    from ..core import deadline

    outs_d, outs_i = [], []
    for s0 in range(0, q.shape[0], chunk):
        deadline.checkpoint(
            res, partial=lambda: deadline.partial_topk(outs_d, outs_i))
        d_c, i_c = fn(q[s0 : s0 + chunk], s0)
        outs_d.append(d_c)
        outs_i.append(i_c)
    if len(outs_d) == 1:
        return outs_d[0], outs_i[0]
    import jax.numpy as jnp

    return jnp.concatenate(outs_d), jnp.concatenate(outs_i)


def shard_map_compat(f, mesh, in_specs, out_specs, check=False):
    """``jax.shard_map`` with its replication check spelled once
    (``check_vma``)."""
    import jax

    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check)


def in_jax_trace() -> bool:
    """True when called during a jax trace (jit/vmap/...). Used to gate
    side-effecting caches: storing traced arrays on a Python object leaks
    tracers out of the transformation."""
    from jax._src.core import trace_state_clean

    return not trace_state_clean()


def use_compile_cache(root: str) -> str:
    """Place JAX's persistent compilation cache before the first compile.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is where the cache lives:
    JAX reads it itself and nothing is set in code. Otherwise the cache
    sits at a fixed ``.jax_cache/`` under ``root`` (the checkout) — a
    fixed path, because the path is part of the cache key. Returns the
    directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = os.path.join(os.path.abspath(root), ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
