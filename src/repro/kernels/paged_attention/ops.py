"""Jitted public wrapper for the fused SOCKET paged-attention kernel.

Accepts the serving engine's natural layouts (5-D decode query, paged
pool leaves, per-request block table / length / budget vectors) and
launches :func:`paged_attention_pallas`.  ``interpret=None`` (the
default) compiles with Mosaic on a TPU backend and interprets elsewhere
(:func:`repro.kernels.common.resolve_interpret`).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.common import resolve_interpret
from repro.kernels.paged_attention.paged_attention import (
    paged_attention_pallas)
from repro.kernels.paged_attention.paged_hard_lsh import paged_hard_lsh_pallas
from repro.kernels.paged_attention.paged_quest import paged_quest_pallas
from repro.kernels.paged_attention.paged_ring import paged_ring_pallas


@functools.partial(jax.jit, static_argnames=(
    "num_tables", "num_planes", "tau", "scale", "sink_tokens",
    "window_tokens", "interpret", "with_selection"))
def _attend_flat(q, k_pages, v_pages, bits_pages, vnorm_pages, u, bt,
                 length, budget, k_scale, v_scale, *, num_tables,
                 num_planes, tau, scale, sink_tokens, window_tokens,
                 interpret, with_selection):
    return paged_attention_pallas(
        q, k_pages, v_pages, bits_pages, vnorm_pages, u, bt, length, budget,
        num_tables=num_tables, num_planes=num_planes, tau=tau, scale=scale,
        sink_tokens=sink_tokens, window_tokens=window_tokens,
        interpret=interpret, with_selection=with_selection,
        k_scale=k_scale, v_scale=v_scale)


def paged_socket_attend(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                        bits_pages: jax.Array, vnorm_pages: jax.Array,
                        u: jax.Array, block_table: jax.Array, *,
                        length, budget, num_tables: int, num_planes: int,
                        tau: float, scale: float, sink_tokens: int,
                        window_tokens: int,
                        interpret: Optional[bool] = None,
                        with_selection: bool = False,
                        k_scale: Optional[jax.Array] = None,
                        v_scale: Optional[jax.Array] = None):
    """Fused score→select→attend over the paged pool for one decode step.

    Shapes:
      q            (B, KVH, G, 1, hd) or (B, KVH, G, hd)
      k/v_pages    (NB, KVH, bs, hd)  (bf16/int8/fp8 storage)
      bits_pages   uint32 (NB, KVH, bs, W)
      vnorm_pages  (NB, KVH, bs)
      u            f32 (B, KVH, GS, L, P)  (GS=1 for pooled selection)
      block_table  int32 (B, nb)
      length       int32 scalar or (B,)
      budget       int32 scalar or (B,)  (dynamic top-k budget, <= cap)
      k/v_scale    (NB, KVH, bs) per-row dequant scales (quantized pools
                   only — both or neither; dequantized in-kernel)

    Returns attention output in q's layout (f32), plus the int32
    ``(B, KVH, nb, bs)`` selection mask when ``with_selection``.
    """
    interpret = resolve_interpret(interpret)
    orig5 = q.ndim == 5
    if orig5:
        b, kvh, g, t, hd = q.shape
        assert t == 1
        q = q.reshape(b, kvh, g, hd)
    b = q.shape[0]
    length = jnp.broadcast_to(jnp.asarray(length, jnp.int32), (b,))
    budget = jnp.broadcast_to(jnp.asarray(budget, jnp.int32), (b,))
    out = _attend_flat(
        q, k_pages, v_pages, bits_pages, vnorm_pages, u, block_table,
        length, budget, k_scale, v_scale,
        num_tables=num_tables, num_planes=num_planes,
        tau=float(tau), scale=float(scale), sink_tokens=int(sink_tokens),
        window_tokens=int(window_tokens), interpret=interpret,
        with_selection=with_selection)
    if with_selection:
        out, sel = out
        sel = sel.reshape(*sel.shape[:2], -1).astype(bool)  # (B,KVH,N)
    if orig5:
        out = out[:, :, :, None]                            # (B,KVH,G,1,hd)
    return (out, sel) if with_selection else out


@functools.partial(jax.jit, static_argnames=(
    "num_tables", "num_planes", "scale", "sink_tokens", "window_tokens",
    "interpret", "with_selection"))
def _hard_lsh_flat(q, k_pages, v_pages, bits_pages, vnorm_pages, u_signs,
                   bt, length, budget, k_scale, v_scale, *, num_tables,
                   num_planes, scale, sink_tokens, window_tokens, interpret,
                   with_selection):
    return paged_hard_lsh_pallas(
        q, k_pages, v_pages, bits_pages, vnorm_pages, u_signs, bt, length,
        budget, num_tables=num_tables, num_planes=num_planes, scale=scale,
        sink_tokens=sink_tokens, window_tokens=window_tokens,
        interpret=interpret, with_selection=with_selection,
        k_scale=k_scale, v_scale=v_scale)


def paged_hard_lsh_attend(q: jax.Array, k_pages: jax.Array,
                          v_pages: jax.Array, bits_pages: jax.Array,
                          vnorm_pages: jax.Array, u_signs: jax.Array,
                          block_table: jax.Array, *, length, budget,
                          num_tables: int, num_planes: int, scale: float,
                          sink_tokens: int, window_tokens: int,
                          interpret: Optional[bool] = None,
                          with_selection: bool = False,
                          k_scale: Optional[jax.Array] = None,
                          v_scale: Optional[jax.Array] = None):
    """Fused hard-collision score→select→attend for one decode step.

    Same shapes as :func:`paged_socket_attend` except the query-side
    hash is ``u_signs`` — f32 ±1 plane signs ``(B, KVH, GS, L, P)``
    (``where(u >= 0, +1, -1)`` of the soft hash).
    """
    interpret = resolve_interpret(interpret)
    orig5 = q.ndim == 5
    if orig5:
        b, kvh, g, t, hd = q.shape
        assert t == 1
        q = q.reshape(b, kvh, g, hd)
    b = q.shape[0]
    length = jnp.broadcast_to(jnp.asarray(length, jnp.int32), (b,))
    budget = jnp.broadcast_to(jnp.asarray(budget, jnp.int32), (b,))
    out = _hard_lsh_flat(
        q, k_pages, v_pages, bits_pages, vnorm_pages, u_signs, block_table,
        length, budget, k_scale, v_scale,
        num_tables=num_tables, num_planes=num_planes,
        scale=float(scale), sink_tokens=int(sink_tokens),
        window_tokens=int(window_tokens), interpret=interpret,
        with_selection=with_selection)
    if with_selection:
        out, sel = out
        sel = sel.reshape(*sel.shape[:2], -1).astype(bool)  # (B,KVH,N)
    if orig5:
        out = out[:, :, :, None]                            # (B,KVH,G,1,hd)
    return (out, sel) if with_selection else out


@functools.partial(jax.jit, static_argnames=(
    "page_size", "scale", "sink_tokens", "window_tokens", "interpret",
    "with_selection"))
def _quest_flat(q, k_pages, v_pages, kmin_pages, kmax_pages, bt, length,
                page_budget, k_scale, v_scale, *, page_size, scale,
                sink_tokens, window_tokens, interpret, with_selection):
    return paged_quest_pallas(
        q, k_pages, v_pages, kmin_pages, kmax_pages, bt, length,
        page_budget, page_size=page_size, scale=scale,
        sink_tokens=sink_tokens, window_tokens=window_tokens,
        interpret=interpret, with_selection=with_selection,
        k_scale=k_scale, v_scale=v_scale)


def paged_quest_attend(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                       kmin_pages: jax.Array, kmax_pages: jax.Array,
                       block_table: jax.Array, *, length, page_budget,
                       page_size: int, scale: float, sink_tokens: int,
                       window_tokens: int,
                       interpret: Optional[bool] = None,
                       with_selection: bool = False,
                       k_scale: Optional[jax.Array] = None,
                       v_scale: Optional[jax.Array] = None):
    """Fused page-granular Quest select→attend for one decode step.

    Shapes:
      q              (B, KVH, G, 1, hd) or (B, KVH, G, hd)
      k/v_pages      (NB, KVH, bs, hd)  (bf16/int8/fp8 storage)
      kmin/kmax      (NB, KVH, bs / page_size, hd) per-page key bounds
                     (over *dequantized* keys under quantized storage)
      block_table    int32 (B, nb)
      length         int32 scalar or (B,)
      page_budget    int scalar or (B,) — pages to attend (the static
                     ``baselines.quest.page_budget``)
      k/v_scale      (NB, KVH, bs) per-row dequant scales (quantized
                     pools only — both or neither)
    """
    interpret = resolve_interpret(interpret)
    orig5 = q.ndim == 5
    if orig5:
        b, kvh, g, t, hd = q.shape
        assert t == 1
        q = q.reshape(b, kvh, g, hd)
    b = q.shape[0]
    length = jnp.broadcast_to(jnp.asarray(length, jnp.int32), (b,))
    page_budget = jnp.broadcast_to(jnp.asarray(page_budget, jnp.int32), (b,))
    out = _quest_flat(
        q, k_pages, v_pages, kmin_pages, kmax_pages, block_table, length,
        page_budget, k_scale, v_scale,
        page_size=int(page_size), scale=float(scale),
        sink_tokens=int(sink_tokens), window_tokens=int(window_tokens),
        interpret=interpret, with_selection=with_selection)
    if with_selection:
        out, sel = out
        sel = sel.reshape(*sel.shape[:2], -1).astype(bool)  # (B,KVH,N)
    if orig5:
        out = out[:, :, :, None]                            # (B,KVH,G,1,hd)
    return (out, sel) if with_selection else out


@functools.partial(jax.jit, static_argnames=(
    "window", "softcap", "scale", "interpret"))
def _ring_flat(q, k_pages, v_pages, bt, pos, k_scale, v_scale, *, window,
               softcap, scale, interpret):
    return paged_ring_pallas(q, k_pages, v_pages, bt, pos, window=window,
                             softcap=softcap, scale=scale,
                             interpret=interpret,
                             k_scale=k_scale, v_scale=v_scale)


def paged_ring_attend(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                      block_table: jax.Array, *, pos, window: int,
                      softcap: float, scale: float,
                      interpret: Optional[bool] = None,
                      k_scale: Optional[jax.Array] = None,
                      v_scale: Optional[jax.Array] = None):
    """Fused sliding-window decode over the circular page list.

    Shapes:
      q            (B, KVH, G, 1, hd) or (B, KVH, G, hd)
      k/v_pages    (NB, KVH, bs, hd)  (bf16/int8/fp8 storage)
      block_table  int32 (B, ring_blocks) — the ring slice of the table
      pos          int32 scalar or (B,) — the decode token's position
                   (already written to its ring slot)
      k/v_scale    (NB, KVH, bs) per-row dequant scales (quantized pools
                   only — both or neither; dequantized in-kernel)
    """
    interpret = resolve_interpret(interpret)
    orig5 = q.ndim == 5
    if orig5:
        b, kvh, g, t, hd = q.shape
        assert t == 1
        q = q.reshape(b, kvh, g, hd)
    b = q.shape[0]
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    out = _ring_flat(q, k_pages, v_pages, block_table, pos, k_scale, v_scale,
                     window=int(window), softcap=float(softcap),
                     scale=float(scale), interpret=interpret)
    if orig5:
        out = out[:, :, :, None]                            # (B,KVH,G,1,hd)
    return out
