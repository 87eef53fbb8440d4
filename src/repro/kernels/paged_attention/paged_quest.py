"""Pallas TPU kernel: fused Quest paged decode attention.

One decode step of the Quest baseline over the serving engine's paged
pool, streaming each request's pages once through VMEM via the block
table (the same two-phase scalar-prefetch layout as the fused SOCKET
kernel) with **page-granular** selection:

1. **Score pass** (grid phase 0): each pool block carries ``block_size /
   page_size`` min/max stat rows (the ``kmin``/``kmax`` leaves); the
   per-page upper bound ``sum_d max(q_d * kmin_d, q_d * kmax_d)`` is
   summed over the GQA group and appended to a VMEM page-score ring
   ``eff (nb, pages_per_block)`` with the sink/window ``+FLT_MAX``
   forcing and past-``length`` ``-1e30`` overlays of
   :func:`repro.baselines.quest.select_tokens`.
2. **Select** (phase 1, first block): the 32-step radix descent finds
   the exact ``page_budget``-th largest page score (the shared
   :func:`repro.baselines.quest.page_budget`), ties resolved in flat
   page order to replicate ``jax.lax.top_k``'s stable semantics.
3. **Attend pass** (phase 1): each block's page-selection mask is
   reconstructed from the threshold (+ SMEM tie counter), expanded to
   rows (a row attends iff its page is selected AND its position is
   live), and the selected rows fold into the flash-style online
   softmax.

Unlike SOCKET's token selection, pages past ``length`` are *not*
filtered out of the selection itself — ``lax.top_k`` in the reference
takes ``page_budget`` pages unconditionally and row validity is applied
afterwards (``idx < length``), which the kernel mirrors exactly.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import (NEG_INF, NN, finish_softmax, fold_page,
                                  head_row, init_softmax)
from repro.kernels.paged_attention.paged_attention import (
    FLT_MAX, _sort_key, count_keys, radix_threshold, tie_rank)

__all__ = ["paged_quest_pallas"]


def _quest_kernel(bt_ref, len_ref, bud_ref,                 # scalar prefetch
                  q_ref, kmin_ref, kmax_ref, k_ref, v_ref,
                  *rest, page_size: int, scale: float, sink: int,
                  window: int, block_size: int, num_seq_blocks: int,
                  with_selection: bool, quantized: bool = False):
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
    ppb = block_size // page_size

    # ---- phase 0: score this block's pages into the VMEM ring -----------
    @pl.when(phase == 0)
    def _score():
        q = q_ref[0, 0].astype(jnp.float32)       # (G, hd)
        kmin = kmin_ref[0, 0].astype(jnp.float32)  # (ppb, hd)
        kmax = kmax_ref[0, 0].astype(jnp.float32)
        scores = jnp.zeros((ppb,), jnp.float32)
        for gi in range(q_ref.shape[2]):          # static GQA group loop
            qg = q[gi][None, :]                   # (1, hd)
            scores = scores + jnp.sum(
                jnp.maximum(kmin * qg, kmax * qg), axis=-1)
        page_start = (jax.lax.broadcasted_iota(jnp.int32, (ppb, 1), 0)
                      .reshape(ppb) * page_size + i * block_size)
        forced = (page_start < sink) | \
            (page_start >= length - window - page_size)
        eff = jnp.where(forced, jnp.float32(FLT_MAX), scores)
        eff = jnp.where(page_start < length, eff, jnp.float32(NEG_INF))
        eff_scr[pl.ds(i, 1), :] = eff.reshape(1, ppb)

    # ---- phase 1, first block: radix-select the page-budget threshold ---
    @pl.when((phase == 1) & (i == 0))
    def _select():
        bud = bud_ref[b]
        thr = radix_threshold(eff_scr, bud)
        thr_scr[0] = thr
        ties_scr[0] = bud - count_keys(eff_scr, lambda k: k > thr)
        cnt_scr[0] = 0
        init_softmax(m_scr, l_scr, acc_scr)

    # ---- phase 1: masked online-softmax over this K/V block -------------
    @pl.when(phase == 1)
    def _attend():
        keys = _sort_key(eff_scr[pl.ds(i, 1), :])  # (1, ppb)
        thr = thr_scr[0]
        gt = keys > thr
        eq = keys == thr
        rank = cnt_scr[0] + tie_rank(eq)
        sel_page = gt | (eq & (rank < ties_scr[0]))
        cnt_scr[0] = cnt_scr[0] + jnp.sum(eq.astype(jnp.int32))

        # expand the page mask to rows via a one-hot matmul (row r belongs
        # to local page r // page_size) — reshape-free for Mosaic
        cc = jax.lax.broadcasted_iota(jnp.int32, (ppb, block_size), 0)
        rr = jax.lax.broadcasted_iota(jnp.int32, (ppb, block_size), 1)
        expand = ((rr // page_size) == cc).astype(jnp.float32)
        row_sel = jax.lax.dot_general(sel_page.astype(jnp.float32), expand,
                                      NN) > 0.5                # (1, bs)
        pos = (jax.lax.broadcasted_iota(jnp.int32, (1, block_size), 1)
               + i * block_size)
        sel = row_sel & (pos < length)
        if with_selection:
            sel_ref[0, 0, pl.ds(i, 1), :] = sel.astype(jnp.int32)

        # quantized pages: the kmin/kmax stats already bound the
        # *dequantized* keys (cfg.quest.stats_from_quantized), so scoring
        # is untouched by the in-register dequant
        fold_page(q_ref[0, 0].astype(jnp.float32), k_ref[0, 0], v_ref[0, 0],
                  sel, m_scr, l_scr, acc_scr, scale=scale,
                  k_scale=head_row(ks_ref, h) if quantized else None,
                  v_scale=head_row(vs_ref, h) if quantized else None)

        @pl.when(i == num_seq_blocks - 1)
        def _done():
            out_ref[0, 0] = finish_softmax(l_scr, acc_scr).astype(
                out_ref.dtype)


def paged_quest_pallas(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                       kmin_pages: jax.Array, kmax_pages: jax.Array,
                       block_table: jax.Array, length: jax.Array,
                       page_budget: jax.Array, *, page_size: int,
                       scale: float, sink_tokens: int, window_tokens: int,
                       interpret: bool,
                       with_selection: bool = False,
                       k_scale=None, v_scale=None):
    """Launch the fused Quest kernel.

    Args:
      q:             (B, KVH, G, hd) query heads for this KV head group.
      k/v_pages:     (NB, KVH, bs, hd) paged pool leaves (bf16/int8/fp8).
      k/v_scale:     (NB, KVH, bs) per-row dequant scales — both or
                     neither; when given the attend pass dequantizes
                     in-register.
      kmin/kmax_pages: (NB, KVH, bs / page_size, hd) per-page key bounds.
      block_table:   int32 (B, nb) physical block ids (trash-padded).
      length:        int32 (B,) live context length per request.
      page_budget:   int32 (B,) pages to select per request (the static
                     ``baselines.quest.page_budget``; vector for launch
                     symmetry with the token kernels).

    Returns:
      f32 (B, KVH, G, hd) attention output; with ``with_selection`` also
      an int32 (B, KVH, nb, bs) selected-rows mask (test/debug only).
    """
    b, kvh, g, hd = q.shape
    bs = k_pages.shape[2]
    nb = block_table.shape[1]
    if v_pages.shape[2] != bs:
        raise ValueError("page pools disagree on block_size")
    if bs % page_size:
        raise ValueError(
            f"page_size {page_size} must divide block_size {bs}")
    ppb = bs // page_size
    if kmin_pages.shape[2] != ppb or kmax_pages.shape[2] != ppb:
        raise ValueError(
            f"kmin/kmax pools must carry {ppb} stat rows per block")
    if (k_scale is None) != (v_scale is None):
        raise ValueError("k_scale/v_scale must be given together")

    kernel = functools.partial(
        _quest_kernel, page_size=int(page_size), scale=float(scale),
        sink=int(sink_tokens), window=int(window_tokens), block_size=bs,
        num_seq_blocks=nb, with_selection=with_selection,
        quantized=k_scale is not None)

    in_specs = [
        pl.BlockSpec((1, 1, g, hd), lambda b, h, ph, i, *s: (b, h, 0, 0)),
        pl.BlockSpec((1, 1, ppb, hd),
                     lambda b, h, ph, i, bt, ln, bd: (bt[b, i * (1 - ph)],
                                                      h, 0, 0)),
        pl.BlockSpec((1, 1, ppb, hd),
                     lambda b, h, ph, i, bt, ln, bd: (bt[b, i * (1 - ph)],
                                                      h, 0, 0)),
        pl.BlockSpec((1, 1, bs, hd),
                     lambda b, h, ph, i, bt, ln, bd: (bt[b, i * ph], h, 0, 0)),
        pl.BlockSpec((1, 1, bs, hd),
                     lambda b, h, ph, i, bt, ln, bd: (bt[b, i * ph], h, 0, 0)),
    ]
    operands = [q, kmin_pages, kmax_pages, k_pages, v_pages]
    if k_scale is not None:
        # per-row dequant scales stream with the K/V pages (attend phase)
        for _ in range(2):
            in_specs.append(pl.BlockSpec(
                (1, kvh, bs),
                lambda b, h, ph, i, bt, ln, bd: (bt[b, i * ph], 0, 0)))
        operands += [k_scale, v_scale]
    out_shape = [jax.ShapeDtypeStruct((b, kvh, g, hd), jnp.float32)]
    out_specs = [pl.BlockSpec((1, 1, g, hd),
                              lambda b, h, ph, i, *s: (b, h, 0, 0))]
    if with_selection:
        out_shape.append(jax.ShapeDtypeStruct((b, kvh, nb, bs), jnp.int32))
        # the whole (nb, bs) mask of one (request, head) stays resident
        # and is written row by row in the attend phase
        out_specs.append(pl.BlockSpec((1, 1, nb, bs),
                                      lambda b, h, ph, i, *s: (b, h, 0, 0)))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, kvh, 2, nb),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((nb, ppb), jnp.float32),   # page-score ring
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
      page_budget.astype(jnp.int32), *operands)
    return tuple(out) if with_selection else out[0]
