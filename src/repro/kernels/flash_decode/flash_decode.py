"""Pallas TPU kernel: flash decode over the SOCKET-selected KV subset.

One decode step of GQA attention for a single KV head's group of G query
heads against the K gathered rows (the top-k ∪ sink ∪ window selection).
Mirrors the paper's Triton "Flash Decode" backend: split-K online softmax
with fp32 running (m, l, acc) state.

Grid = (BH, K // block_k); the K axis is the innermost (sequential on TPU)
grid dimension, so the kernel accumulates across K blocks in VMEM scratch
and writes the normalised output on the final block:

  per step  : q (G, hd) resident; k/v block (block_k, hd); mask (block_k,)
  scratch   : m (G,), l (G,), acc (G, hd)  — all fp32
  epilogue  : out = acc / l

VMEM per step at (G=8, hd=256, block_k=512): ~1.3 MiB.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import finish_softmax, fold_page, init_softmax

DEFAULT_BLOCK_K = 512


def _decode_kernel(q_ref, k_ref, v_ref, mask_ref, out_ref, m_scr, l_scr,
                   acc_scr, *, scale: float, num_k_blocks: int):
    kb = pl.program_id(1)

    @pl.when(kb == 0)
    def _init():
        init_softmax(m_scr, l_scr, acc_scr)

    fold_page(q_ref[0].astype(jnp.float32), k_ref[0], v_ref[0],
              mask_ref[0] != 0, m_scr, l_scr, acc_scr, scale=scale)

    @pl.when(kb == num_k_blocks - 1)
    def _done():
        out_ref[0] = finish_softmax(l_scr, acc_scr).astype(out_ref.dtype)


def flash_decode_pallas(q: jax.Array, k: jax.Array, v: jax.Array,
                        mask: jax.Array, *, scale: float,
                        block_k: int = DEFAULT_BLOCK_K,
                        interpret: bool) -> jax.Array:
    """q (BH, G, hd); k/v (BH, K, hd); mask (BH, K) -> f32 (BH, G, hd).

    ``K`` need not divide ``block_k``: the tail (and a whole short
    ``K < block_k`` buffer) is padded to the block boundary with
    mask-off rows, which the kernel already scores as ``-inf``.
    """
    bh, g, hd = q.shape
    kk = k.shape[1]
    blk = max(1, min(block_k, kk))
    pad = (-kk) % blk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0)))
        mask = jnp.pad(mask, ((0, 0), (0, pad)))
    block_k = blk
    nkb = (kk + pad) // block_k
    kernel = functools.partial(_decode_kernel, scale=float(scale),
                               num_k_blocks=nkb)
    return pl.pallas_call(
        kernel,
        grid=(bh, nkb),
        in_specs=[
            pl.BlockSpec((1, g, hd), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, 1, block_k), lambda b, i: (b, 0, i)),
        ],
        out_specs=pl.BlockSpec((1, g, hd), lambda b, i: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, g, hd), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),     # m
            pltpu.VMEM((g, 1), jnp.float32),     # l
            pltpu.VMEM((g, hd), jnp.float32),    # acc
        ],
        interpret=interpret,
    )(q, k, v, mask.astype(jnp.int32).reshape(bh, 1, kk + pad))
