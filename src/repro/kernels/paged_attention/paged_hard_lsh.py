"""Pallas TPU kernel: fused hard-LSH paged decode attention.

The tau -> 0 ablation of the fused SOCKET kernel: identical two-phase
streaming over the block table (scalar-prefetch index maps, VMEM score
ring, exact radix-select of the per-request budget, selected-rows-only
online-softmax rescan — all shared with
:mod:`~repro.kernels.paged_attention.paged_attention` via
``_fused_kernel(mode="hard_lsh")``), but phase 0 scores by **hard
collision counting** instead of the soft kernel estimate:

    count_j = sum_l 1[ every plane sign of table l agrees with the query ]

evaluated in-register from the same packed uint32 hash words.  The query
side is the host-precomputed ±1 sign pattern of its soft hash
(``sign(tanh(Wq))`` == ``sign(Wq)``), one per q head — the backend's
``u_signs = where(u >= 0, +1, -1)``.  A table collides iff the ±1 inner
product over its P planes attains exactly P, so the agreement test is a
single einsum + compare, integer-exact in f32.

Padding contract: ``num_words`` rounds the packed width up so W*32 is a
multiple of P; the padded table slots unpack to all ``-1`` signs
(packed bits are zero-padded), and the launcher zero-pads ``u_signs``
there — agreement is then 0 < P, so padding tables never count (the
hard-LSH analogue of the socket kernel's ``logZ = +inf`` padding).

Selection and attention semantics are exactly the backend's XLA path:
``value_aware_topk`` over ``count * ||v||`` with sink/window forcing,
ragged lengths and per-request dynamic budgets, then masked
online-softmax attention over the selected rows.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.paged_attention.paged_attention import (
    _fused_call, _fused_kernel)

__all__ = ["paged_hard_lsh_pallas"]


def paged_hard_lsh_pallas(q: jax.Array, k_pages: jax.Array,
                          v_pages: jax.Array, bits_pages: jax.Array,
                          vnorm_pages: jax.Array, u_signs: jax.Array,
                          block_table: jax.Array, length: jax.Array,
                          budget: jax.Array, *, num_tables: int,
                          num_planes: int, scale: float,
                          sink_tokens: int, window_tokens: int,
                          interpret: bool,
                          with_selection: bool = False,
                          k_scale=None, v_scale=None):
    """Launch the fused hard-LSH kernel.

    Args:
      q:           (B, KVH, G, hd) query heads for this KV head group.
      k/v_pages:   (NB, KVH, bs, hd) paged pool leaves (bf16/int8/fp8).
      k/v_scale:   (NB, KVH, bs) per-row dequant scales — both or neither;
                   when given the attend pass dequantizes in-register.
      bits_pages:  uint32 (NB, KVH, bs, W) packed sign bits.
      vnorm_pages: (NB, KVH, bs) value norms (any float dtype).
      u_signs:     f32 ±1 (B, KVH, G, L, P) query hash plane signs.
      block_table: int32 (B, nb) physical block ids (trash-padded).
      length:      int32 (B,) live context length per request.
      budget:      int32 (B,) dynamic top-k budget per request.

    Returns:
      f32 (B, KVH, G, hd) attention output; with ``with_selection`` also
      an int32 (B, KVH, nb, bs) selection mask (test/debug only).
    """
    bs, w = bits_pages.shape[2], bits_pages.shape[3]
    nb = block_table.shape[1]
    b, kvh, gs, l, p = u_signs.shape
    if l != num_tables or p != num_planes:
        raise ValueError("u_signs shape mismatch")
    if (w * 32) % num_planes:
        raise ValueError(
            f"packed width {w*32} bits not a multiple of P={num_planes}")
    if k_pages.shape[2] != bs or v_pages.shape[2] != bs \
            or vnorm_pages.shape[2] != bs:
        raise ValueError("page pools disagree on block_size")
    if (k_scale is None) != (v_scale is None):
        raise ValueError("k_scale/v_scale must be given together")
    l_pad = (w * 32) // num_planes

    # zero-pad the query signs over the alignment tables: padded key bits
    # unpack to -1 signs, and sum(-1 * 0) == 0 < P never counts a table.
    u_pad = jnp.pad(u_signs.astype(jnp.float32),
                    ((0, 0), (0, 0), (0, 0), (0, l_pad - l), (0, 0)))
    logz_pad = jnp.zeros(u_pad.shape[:-1], jnp.float32)   # unused in-kernel

    kernel = functools.partial(
        _fused_kernel, num_planes=num_planes, l_pad=l_pad, tau=1.0,
        scale=float(scale), sink=int(sink_tokens),
        window=int(window_tokens), block_size=bs, num_seq_blocks=nb,
        with_selection=with_selection, mode="hard_lsh",
        quantized=k_scale is not None)
    return _fused_call(kernel, q, bits_pages, vnorm_pages,
                       u_pad.reshape(b, kvh, gs, l_pad * p), logz_pad,
                       k_pages, v_pages, block_table, length, budget,
                       with_selection=with_selection, interpret=interpret,
                       k_scale=k_scale, v_scale=v_scale)
