"""Pallas TPU kernel: causal flash-attention forward (prefill path).

Classic FlashAttention-2 style tiling: grid = (BH, Q blocks, K blocks) with
the K axis innermost/sequential; fp32 (m, l, acc) scratch carried across K
blocks, normalised write-back on the last visited K block.  Supports an
optional sliding window (gemma3/mixtral local layers).

Block skipping: K blocks strictly above the causal diagonal (or entirely
outside the window) contribute nothing; their work is masked out.  (A
production variant would prune them from the grid with a custom index map;
masked execution keeps the kernel simple and the FLOP accounting explicit —
see EXPERIMENTS.md §Perf.)

VMEM at (block_q=512, block_k=512, hd=256): q/k/v tiles 3·512·256·4 B ≈
1.5 MiB + acc 512·256·4 B — comfortable.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 512
NEG_INF = -1e30


def _prefill_kernel(q_ref, k_ref, v_ref, out_ref, m_scr, l_scr, acc_scr, *,
                    scale: float, block_q: int, block_k: int,
                    num_k_blocks: int, window: int):
    qb = pl.program_id(1)
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_pos = qb * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    k_pos = kb * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    mask = k_pos <= q_pos
    if window > 0:
        mask &= (q_pos - k_pos) < window

    q = q_ref[0].astype(jnp.float32)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.where(mask, jnp.exp(s - m_new[:, None]), 0.0)

    l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=-1)
    acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())))
    m_scr[...] = m_new

    @pl.when(kb == num_k_blocks - 1)
    def _done():
        out_ref[0] = (acc_scr[...] /
                      jnp.maximum(l_scr[...], 1e-30)[:, None]
                      ).astype(out_ref.dtype)


def flash_prefill_pallas(q: jax.Array, k: jax.Array, v: jax.Array, *,
                         scale: float, window: int = 0,
                         block_q: int = DEFAULT_BLOCK_Q,
                         block_k: int = DEFAULT_BLOCK_K,
                         interpret: bool) -> jax.Array:
    """q/k/v (BH, S, hd) -> f32 (BH, S, hd) causal attention."""
    bh, s, hd = q.shape
    if s % block_q or s % block_k:
        raise ValueError(f"S={s} must be a multiple of block sizes")
    nkb = s // block_k
    kernel = functools.partial(
        _prefill_kernel, scale=float(scale), block_q=block_q,
        block_k=block_k, num_k_blocks=nkb, window=int(window))
    return pl.pallas_call(
        kernel,
        grid=(bh, s // block_q, nkb),
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, hd), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
