"""Pallas TPU kernel: sliding-window (ring) paged decode attention.

Hybrid models' local layers keep only the last ``sliding_window`` tokens
in a circular page list (``RingView``): slot ``s`` of the ring holds the
most recent token with ``position % capacity == s``.  The XLA path
materializes the ring K/V via a pool gather and applies the window mask
in plain jnp; this kernel instead streams the ring blocks straight from
the paged pool via the block table (scalar-prefetch index maps) and
applies the mask in-register — zero gathered bytes per step.

Per (request, KV head) the grid walks the ring blocks once; for each
slot the kernel reconstructs the position of the token currently stored
there,

    ring_pos = pos - ((pos - slot) % capacity)

(the newest absolute position congruent to the slot; ``%`` is jnp's
non-negative modulo), masks slots that are empty (``ring_pos < 0``) or
aged out of the window (``pos - ring_pos >= window``), and folds the
live rows into a flash-style online softmax.  Gemma-style logit
softcapping (``c * tanh(s / c)``) is applied **before** masking, exactly
as the XLA reference; ``softcap == 0`` statically disables it.

There is no selection phase — every in-window token attends — so the
grid is single-phase: (B, KVH, ring_blocks).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import (finish_softmax, fold_page, head_row,
                                  init_softmax)

__all__ = ["paged_ring_pallas"]


def _ring_kernel(bt_ref, pos_ref,                           # scalar prefetch
                 q_ref, k_ref, v_ref, *rest, scale: float, window: int,
                 softcap: float, block_size: int, ring_blocks: int,
                 quantized: bool = False):
    if quantized:
        ks_ref, vs_ref = rest[0], rest[1]
        rest = rest[2:]
    out_ref, m_scr, l_scr, acc_scr = rest
    b = pl.program_id(0)
    h = pl.program_id(1)
    i = pl.program_id(2)
    pos = pos_ref[b]
    cap = ring_blocks * block_size

    @pl.when(i == 0)
    def _init():
        init_softmax(m_scr, l_scr, acc_scr)

    slot = jax.lax.broadcasted_iota(jnp.int32, (1, block_size), 1) \
        + i * block_size
    ring_pos = pos - ((pos - slot) % cap)
    valid = (ring_pos >= 0) & (pos - ring_pos < window)      # (1, bs)

    # int8/fp8 ring pages: per-row absmax scales ride along as (bs,)
    # leaves — dequantized in-register, never in HBM
    fold_page(q_ref[0, 0].astype(jnp.float32), k_ref[0, 0], v_ref[0, 0],
              valid, m_scr, l_scr, acc_scr, scale=scale, softcap=softcap,
              k_scale=head_row(ks_ref, h) if quantized else None,
              v_scale=head_row(vs_ref, h) if quantized else None)

    @pl.when(i == ring_blocks - 1)
    def _done():
        out_ref[0, 0] = finish_softmax(l_scr, acc_scr).astype(out_ref.dtype)


def paged_ring_pallas(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                      block_table: jax.Array, pos: jax.Array, *,
                      window: int, softcap: float, scale: float,
                      interpret: bool, k_scale=None, v_scale=None):
    """Launch the ring decode kernel.

    Args:
      q:           (B, KVH, G, hd) query heads for this KV head group.
      k/v_pages:   (NB, KVH, bs, hd) paged pool leaves (bf16/int8/fp8).
      k/v_scale:   (NB, KVH, bs) per-row dequant scales — both or neither;
                   when given each streamed page dequantizes in-register.
      block_table: int32 (B, ring_blocks) — the circular page list only
                   (callers slice the full table to the ring geometry).
      pos:         int32 (B,) absolute position of the decode token (the
                   query's own position; it has already been written to
                   its ring slot).
      window:      sliding-window length in tokens.
      softcap:     attention logit softcap (0.0 disables).

    Returns f32 (B, KVH, G, hd) attention output.
    """
    b, kvh, g, hd = q.shape
    bs = k_pages.shape[2]
    rb = block_table.shape[1]
    if v_pages.shape[2] != bs:
        raise ValueError("page pools disagree on block_size")
    if (k_scale is None) != (v_scale is None):
        raise ValueError("k_scale/v_scale must be given together")

    kernel = functools.partial(
        _ring_kernel, scale=float(scale), window=int(window),
        softcap=float(softcap), block_size=bs, ring_blocks=rb,
        quantized=k_scale is not None)

    in_specs = [
        pl.BlockSpec((1, 1, g, hd), lambda b, h, i, *s: (b, h, 0, 0)),
        pl.BlockSpec((1, 1, bs, hd),
                     lambda b, h, i, bt, ps: (bt[b, i], h, 0, 0)),
        pl.BlockSpec((1, 1, bs, hd),
                     lambda b, h, i, bt, ps: (bt[b, i], h, 0, 0)),
    ]
    operands = [q, k_pages, v_pages]
    if k_scale is not None:
        # per-row dequant scales stream with the K/V pages
        for _ in range(2):
            in_specs.append(pl.BlockSpec(
                (1, kvh, bs), lambda b, h, i, bt, ps: (bt[b, i], 0, 0)))
        operands += [k_scale, v_scale]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, kvh, rb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, g, hd), lambda b, h, i, *s: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),      # m
            pltpu.VMEM((g, 1), jnp.float32),      # l
            pltpu.VMEM((g, hd), jnp.float32),     # acc
        ],
    )
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kvh, g, hd), jnp.float32),
        interpret=interpret,
    )(block_table.astype(jnp.int32), pos.astype(jnp.int32), *operands)
