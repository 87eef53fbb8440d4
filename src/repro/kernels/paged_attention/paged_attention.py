"""Pallas TPU kernel: fused SOCKET paged decode attention.

One decode step for the serving engine's paged pool: per (request, KV
head) the kernel streams that request's pages **once through VMEM** via
the block table (scalar-prefetch index maps — the same mechanism as
jax's reference ``paged_attention`` kernel) and performs the whole
SOCKET decode pipeline without materializing scores, indices, or
gathered K/V in HBM:

1. **Score pass** (grid phase 0): for each page, unpack the packed hash
   bits in-register, evaluate the factorized soft-collision score
   (identical math to ``kernels/socket_score``), weight by the value
   norms, overlay the forced sink/recency-window ``+FLT_MAX`` and the
   invalid-slot ``-1e30``, and append the per-token effective score to a
   VMEM scratch ring ``eff (nb, block_size)``.  Only the bits/vnorm
   leaves move — at deployment settings ~64x less HBM traffic than K/V.
2. **Select** (phase 1, first page): a 32-step radix descent over the
   sortable-uint32 view of ``eff`` finds the exact ``budget``-th largest
   value (the per-request dynamic top-k budget, ``k_r = clip(ceil(len_r
   / sparsity), min_k, k_cap)``) — a *threshold*, not an index list, so
   nothing round-trips to the host and no index tensor is written.
   Tie counts are resolved in index order to replicate
   ``jax.lax.top_k``'s stable lowest-index-first semantics bit for bit.
3. **Attend pass** (phase 1): rescan the VMEM score ring page by page,
   reconstruct each page's selection mask from the threshold (+ a
   running tie counter in SMEM), and fold the selected rows of the K/V
   pages into a flash-style online softmax (fp32 running ``m, l, acc``
   exactly as ``kernels/flash_decode``), emitting ``acc / l`` on the
   final page.

Selection semantics are the full ``core.socket.value_aware_topk``
contract: sink + recency-window forcing, per-request ragged budgets
under a static cap, trash-page-0 / not-yet-written slots masked by the
per-request length.  The selected *set* is exactly the reference's
(property-tested in ``tests/test_kernels.py``); the attention output
matches the score→top-k→flash_decode composition to accumulation-order
rounding (the fused kernel folds rows in logical order, the unfused
path in selection-rank order).

Grid = (B, KVH, 2, nb) with the page axis innermost (sequential on
TPU); phase 0 is the score pass, phase 1 the attend pass.  Index maps
pin the K/V page index to ``bt[b, 0]`` during the score phase (and the
bits/vnorm index during the attend phase), so Pallas's revisiting
pipeline fetches each page's K/V exactly once.

Mosaic layout: every per-token vector is a ``(1, bs)`` lane row and
every per-query-head statistic a ``(G, 1)`` column.  Per-head ``(NB,
KVH, bs)`` leaves (value norms, dequant scales) are fetched as whole
``(1, KVH, bs)`` tiles — a ``(1, 1, bs)`` block would put a 1 against
KVH on the sublane axis, which Mosaic refuses — and the head's row is
picked in-register.  The table/plane split of the unpacked hash bits is
a matmul against a 0/1 segment matrix instead of a lane reshape.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import (HIGHEST, NEG_INF, NN, finish_softmax,
                                  fold_page, head_row, init_softmax,
                                  table_scores, unpack_signs)

FLT_MAX = float(np.finfo(np.float32).max)


def _sort_key(eff: jax.Array) -> jax.Array:
    """Order-preserving f32 -> uint32 map (radix-select key space)."""
    u = jax.lax.bitcast_convert_type(eff, jnp.uint32)
    neg = (u >> jnp.uint32(31)) == jnp.uint32(1)
    return u ^ jnp.where(neg, jnp.uint32(0xFFFFFFFF), jnp.uint32(0x80000000))


def _ring_chunk(rows: int, cap: int = 512) -> int:
    """Rows of the score ring one select pass reads at a time: the whole
    ring when it is small, else the largest multiple-of-8 divisor of
    ``rows`` up to ``cap`` (bounds the live key temporaries in VMEM)."""
    if rows <= cap:
        return rows
    for c in range(cap, 7, -8):
        if rows % c == 0:
            return c
    return rows


def count_keys(ring_ref, pred) -> jax.Array:
    """``sum(pred(_sort_key(ring)))`` over the VMEM score ring, chunked
    over its rows."""
    rows = ring_ref.shape[0]
    chunk = _ring_chunk(rows)
    if chunk == rows:
        return jnp.sum(pred(_sort_key(ring_ref[...])).astype(jnp.int32))

    def body(c, acc):
        start = pl.multiple_of(c * chunk, chunk)
        keys = _sort_key(ring_ref[pl.ds(start, chunk), :])
        return acc + jnp.sum(pred(keys).astype(jnp.int32))

    return jax.lax.fori_loop(0, rows // chunk, body, jnp.int32(0))


def radix_threshold(ring_ref, bud) -> jax.Array:
    """Largest uint32 ``T`` with ``count(keys >= T) >= bud`` over the
    ring's sort keys — the ``bud``-th largest key (attained), built
    MSB-first."""
    def body(t, prefix):
        shift = jnp.uint32(31) - t.astype(jnp.uint32)
        cand = prefix | (jnp.uint32(1) << shift)
        cnt = count_keys(ring_ref, lambda k: k >= cand)
        return jnp.where(cnt >= bud, cand, prefix)

    return jax.lax.fori_loop(0, 32, body, jnp.uint32(0))


def tie_rank(eq: jax.Array) -> jax.Array:
    """Exclusive prefix count of a ``(1, n)`` bool row, as int32 —
    a strict lower-triangular matmul (Mosaic has no cumsum)."""
    n = eq.shape[-1]
    r = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    before = (r < c).astype(jnp.float32)
    prior = jax.lax.dot_general(eq.astype(jnp.float32), before, NN,
                                precision=HIGHEST)
    return prior.astype(jnp.int32)


def _fused_kernel(bt_ref, len_ref, bud_ref,                 # scalar prefetch
                  q_ref, bits_ref, vnorm_ref, u_ref, logz_ref, k_ref, v_ref,
                  *rest, num_planes: int, l_pad: int, tau: float,
                  scale: float, sink: int, window: int, block_size: int,
                  num_seq_blocks: int, with_selection: bool,
                  mode: str = "socket", quantized: bool = False):
    if quantized:
        ks_ref, vs_ref = rest[0], rest[1]
        rest = rest[2:]
    if with_selection:
        out_ref, sel_ref = rest[0], rest[1]
        eff_scr, m_scr, l_scr, acc_scr, thr_scr, ties_scr, cnt_scr = rest[2:]
    else:
        out_ref = rest[0]
        eff_scr, m_scr, l_scr, acc_scr, thr_scr, ties_scr, cnt_scr = rest[1:]

    b = pl.program_id(0)
    h = pl.program_id(1)
    phase = pl.program_id(2)
    i = pl.program_id(3)
    length = len_ref[b]

    # ---- phase 0: score this page into the VMEM ring --------------------
    @pl.when(phase == 0)
    def _score():
        # factorized soft-collision score (hard_lsh: collision count —
        # u then holds the query's ±1 plane signs, 0 on padding tables)
        scores = table_scores(
            unpack_signs(bits_ref[0, 0]), u_ref[0, 0],
            logz_ref[0, 0] if mode == "socket" else None,
            num_planes=num_planes, tau=tau)       # (1, bs)
        eff = scores * head_row(vnorm_ref, h)     # (1, bs)

        pos = (jax.lax.broadcasted_iota(jnp.int32, (1, block_size), 1)
               + i * block_size)
        forced = (pos < sink) | (pos >= length - window)
        eff = jnp.where(forced, jnp.float32(FLT_MAX), eff)
        eff = jnp.where(pos < length, eff, jnp.float32(NEG_INF))
        eff_scr[pl.ds(i, 1), :] = eff

    # ---- phase 1, first page: radix-select the budget threshold ---------
    @pl.when((phase == 1) & (i == 0))
    def _select():
        bud = bud_ref[b]
        thr = radix_threshold(eff_scr, bud)
        thr_scr[0] = thr
        ties_scr[0] = bud - count_keys(eff_scr, lambda k: k > thr)
        cnt_scr[0] = 0
        init_softmax(m_scr, l_scr, acc_scr)

    # ---- phase 1: masked online-softmax over this K/V page --------------
    @pl.when(phase == 1)
    def _attend():
        eff = eff_scr[pl.ds(i, 1), :]             # (1, bs)
        keys = _sort_key(eff)
        thr = thr_scr[0]
        gt = keys > thr
        eq = keys == thr
        # stable tie-break by index: position j takes a threshold tie iff
        # (# earlier ties) < ties_needed
        rank = cnt_scr[0] + tie_rank(eq)
        sel = gt | (eq & (rank < ties_scr[0]))
        sel = sel & (eff > jnp.float32(NEG_INF / 2))
        cnt_scr[0] = cnt_scr[0] + jnp.sum(eq.astype(jnp.int32))
        if with_selection:
            sel_ref[0, 0, pl.ds(i, 1), :] = sel.astype(jnp.int32)

        fold_page(q_ref[0, 0].astype(jnp.float32), k_ref[0, 0], v_ref[0, 0],
                  sel, m_scr, l_scr, acc_scr, scale=scale,
                  k_scale=head_row(ks_ref, h) if quantized else None,
                  v_scale=head_row(vs_ref, h) if quantized else None)

        @pl.when(i == num_seq_blocks - 1)
        def _done():
            out_ref[0, 0] = finish_softmax(l_scr, acc_scr).astype(
                out_ref.dtype)


def _fused_call(kernel, q, bits_pages, vnorm_pages, u_flat, logz_pad,
                k_pages, v_pages, block_table, length, budget, *,
                with_selection: bool, interpret: bool,
                k_scale=None, v_scale=None):
    """Shared launch plumbing for the socket/hard_lsh fused kernels: the
    two-phase (score, attend) grid with dual scalar-prefetch index maps
    and the VMEM score ring + online-softmax scratch layout.

    ``u_flat`` is the query hash ``(B, KVH, GS, l_pad * P)`` (tables
    padded to ``l_pad``, flattened table-major like the packed bits).
    ``k_scale``/``v_scale`` (NB, KVH, bs) ride along as extra attend-phase
    page streams when the K/V pool is quantized (int8/fp8 storage)."""
    b, kvh, g, hd = q.shape
    bs, w = bits_pages.shape[2], bits_pages.shape[3]
    nb = block_table.shape[1]
    gs, nbits = u_flat.shape[2:]
    l_pad = logz_pad.shape[-1]

    # K/V pages are pinned to bt[b, 0] during the score phase (and
    # bits/vnorm during the attend phase) so the revisiting pipeline
    # fetches each leaf once per page, not once per phase.
    def score_page(b, h, ph, i, bt, ln, bd):
        return bt[b, i * (1 - ph)]

    def attend_page(b, h, ph, i, bt, ln, bd):
        return bt[b, i * ph]

    in_specs = [
        pl.BlockSpec((1, 1, g, hd), lambda b, h, ph, i, *s: (b, h, 0, 0)),
        pl.BlockSpec((1, 1, bs, w),
                     lambda b, h, *a: (score_page(b, h, *a), h, 0, 0)),
        pl.BlockSpec((1, kvh, bs),
                     lambda b, h, *a: (score_page(b, h, *a), 0, 0)),
        pl.BlockSpec((1, 1, gs, nbits),
                     lambda b, h, ph, i, *s: (b, h, 0, 0)),
        pl.BlockSpec((1, 1, gs, l_pad),
                     lambda b, h, ph, i, *s: (b, h, 0, 0)),
        pl.BlockSpec((1, 1, bs, hd),
                     lambda b, h, *a: (attend_page(b, h, *a), h, 0, 0)),
        pl.BlockSpec((1, 1, bs, hd),
                     lambda b, h, *a: (attend_page(b, h, *a), h, 0, 0)),
    ]
    operands = [q, bits_pages, vnorm_pages, u_flat, logz_pad,
                k_pages, v_pages]
    if k_scale is not None:
        # per-row dequant scales stream with the K/V pages (attend phase)
        for _ in range(2):
            in_specs.append(pl.BlockSpec(
                (1, kvh, bs),
                lambda b, h, *a: (attend_page(b, h, *a), 0, 0)))
        operands += [k_scale, v_scale]
    out_shape = [jax.ShapeDtypeStruct((b, kvh, g, hd), jnp.float32)]
    out_specs = [pl.BlockSpec((1, 1, g, hd),
                              lambda b, h, ph, i, *s: (b, h, 0, 0))]
    if with_selection:
        # the whole (nb, bs) mask of one (request, head) stays resident
        # and is written row by row in the attend phase
        out_shape.append(jax.ShapeDtypeStruct((b, kvh, nb, bs), jnp.int32))
        out_specs.append(pl.BlockSpec((1, 1, nb, bs),
                                      lambda b, h, ph, i, *s: (b, h, 0, 0)))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, kvh, 2, nb),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((nb, bs), jnp.float32),    # eff score ring
            pltpu.VMEM((g, 1), jnp.float32),      # m
            pltpu.VMEM((g, 1), jnp.float32),      # l
            pltpu.VMEM((g, hd), jnp.float32),     # acc
            pltpu.SMEM((1,), jnp.uint32),         # threshold key
            pltpu.SMEM((1,), jnp.int32),          # ties still to take
            pltpu.SMEM((1,), jnp.int32),          # ties consumed so far
        ],
    )
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec, out_shape=out_shape,
        interpret=interpret,
    )(block_table.astype(jnp.int32), length.astype(jnp.int32),
      budget.astype(jnp.int32), *operands)
    return tuple(out) if with_selection else out[0]


def paged_attention_pallas(q: jax.Array, k_pages: jax.Array,
                           v_pages: jax.Array, bits_pages: jax.Array,
                           vnorm_pages: jax.Array, u: jax.Array,
                           block_table: jax.Array, length: jax.Array,
                           budget: jax.Array, *, num_tables: int,
                           num_planes: int, tau: float, scale: float,
                           sink_tokens: int, window_tokens: int,
                           interpret: bool,
                           with_selection: bool = False,
                           k_scale: Optional[jax.Array] = None,
                           v_scale: Optional[jax.Array] = None):
    """Launch the fused kernel.

    Args:
      q:           (B, KVH, G, hd) query heads for this KV head group.
      k/v_pages:   (NB, KVH, bs, hd) paged pool leaves (bf16/int8/fp8).
      k/v_scale:   (NB, KVH, bs) per-row dequant scales — both or neither;
                   when given the attend pass dequantizes in-register.
      bits_pages:  uint32 (NB, KVH, bs, W) packed sign bits.
      vnorm_pages: (NB, KVH, bs) value norms (any float dtype).
      u:           f32 (B, KVH, GS, L, P) query soft-hash (GS=1 pooled).
      block_table: int32 (B, nb) physical block ids (trash-padded).
      length:      int32 (B,) live context length per request.
      budget:      int32 (B,) dynamic top-k budget per request.

    Returns:
      f32 (B, KVH, G, hd) attention output; with ``with_selection`` also
      an int32 (B, KVH, nb, bs) selection mask (test/debug only — it is
      exactly the HBM materialization the production path avoids).
    """
    b, kvh, g, hd = q.shape
    nblocks, _, bs, w = bits_pages.shape
    nb = block_table.shape[1]
    _, _, gs, l, p = u.shape
    if l != num_tables or p != num_planes:
        raise ValueError("u shape mismatch")
    if (w * 32) % num_planes:
        raise ValueError(
            f"packed width {w*32} bits not a multiple of P={num_planes}")
    if k_pages.shape[2] != bs or v_pages.shape[2] != bs \
            or vnorm_pages.shape[2] != bs:
        raise ValueError("page pools disagree on block_size")
    if (k_scale is None) != (v_scale is None):
        raise ValueError("k_scale/v_scale must be given together")
    l_pad = (w * 32) // num_planes

    from repro.core import socket as sk
    logz = sk.log_normalizer(u.astype(jnp.float32), tau)   # (B,KVH,GS,L)
    pad_l = l_pad - l
    u_pad = jnp.pad(u.astype(jnp.float32),
                    ((0, 0), (0, 0), (0, 0), (0, pad_l), (0, 0)))
    logz_pad = jnp.pad(logz, ((0, 0), (0, 0), (0, 0), (0, pad_l)),
                       constant_values=jnp.float32(1e30))

    kernel = functools.partial(
        _fused_kernel, num_planes=num_planes, l_pad=l_pad, tau=float(tau),
        scale=float(scale), sink=int(sink_tokens), window=int(window_tokens),
        block_size=bs, num_seq_blocks=nb, with_selection=with_selection,
        mode="socket", quantized=k_scale is not None)
    return _fused_call(kernel, q, bits_pages, vnorm_pages,
                       u_pad.reshape(b, kvh, gs, l_pad * p), logz_pad,
                       k_pages, v_pages, block_table, length, budget,
                       with_selection=with_selection, interpret=interpret,
                       k_scale=k_scale, v_scale=v_scale)
