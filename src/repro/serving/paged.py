"""Device-side paged cache pool, organised by the per-layer cache plan.

Each layer of the stack resolves to one cache handler
(:func:`repro.models.backends.layer_cache_handler`, mirroring
``cfg.cache_plan()``):

* **paged** (global attention) — every leaf of the backend's
  ``cache_spec`` re-homed with the batch axis replaced by the physical
  block axis and the capacity axis by the block size (divided by the
  leaf's sequence granularity)::

      k / v   : (num_blocks, KVH, block_size, hd)
      bits    : (num_blocks, KVH, block_size, W)   (SOCKET hash bits)
      vnorm   : (num_blocks, KVH, block_size)      (SOCKET value norms)
      kmin/max: (num_blocks, KVH, block_size/ps, hd) (Quest page stats)

* **ring** (sliding-window attention) — K/V pages of the same geometry,
  but addressed circularly through the first ``ring_blocks`` block-table
  entries, so per-slot block demand is bounded by the window.

* **state** (Mamba/SSD) — conv tail + recurrent state as one row per
  decode slot (``(max_batch, ...)``), no block table at all.

Grouped (scan-stacked) layers carry a leading group axis; handler calls
are lifted over it with ``jax.vmap``.  One block id addresses the same
page in every paged/ring layer, so the host allocator
(:mod:`repro.serving.block_pool`) hands out one id list per request for
the whole stack — ring layers simply recycle the list's head.

**Paged-capable backends** (``DecodeBackend.supports_paged``) consume
the pool directly through ``PagedView``/``RingView`` — the engine passes
the pool + block tables into ``decode_step`` and no contiguous K/V view
is ever materialized for global layers (ring views are window-bounded by
construction; state needs no view at all).  For the remaining backends
(dense) the engine falls back to the gather/scatter round trip below:
materialize each slot's contiguous views, run the unmodified decode,
write the updated rows back.  :func:`gather_footprint` quantifies the
per-step traffic of both regimes, per layer kind.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ServingSettings
from repro.models import backends as bk
from repro.models import transformer as tfm

__all__ = ["init_paged_caches", "gather_views", "scatter_token",
           "write_prefill", "clone_block",
           "gather_footprint", "cache_kind_counts", "kv_row_bytes",
           "pool_block_bytes"]


def init_paged_caches(cfg: ModelConfig, serving: ServingSettings):
    """Zero-initialized pool, reusing the model's cache builder with
    batch=num_blocks and capacity=block_size (per-kind layout overrides
    via ``pool=serving`` — see :func:`tfm.init_decode_caches`)."""
    serving.validate()
    return tfm.init_decode_caches(cfg, batch=serving.num_blocks,
                                  capacity=serving.block_size,
                                  pool=serving)


def _map_slots(cfg: ModelConfig, fn, *trees):
    """Apply ``fn(handler, *subtrees)`` per layer slot, vmapped over the
    group axis for the scan-stacked pattern slots."""
    def over(specs, grouped, *subtrees):
        out = {}
        for i, spec in enumerate(specs):
            h = bk.layer_cache_handler(cfg, spec)
            subs = [t[f"slot_{i}"] for t in subtrees]
            out[f"slot_{i}"] = jax.vmap(lambda *xs, _h=h: fn(_h, *xs))(
                *subs) if grouped else fn(h, *subs)
        return out
    return {
        "groups": over(cfg.pattern, True,
                       *[t["groups"] for t in trees]),
        "remainder": over(cfg.remainder, False,
                          *[t["remainder"] for t in trees]),
    }


def gather_views(cfg: ModelConfig, pages, bt: jax.Array):
    """Materialize the ragged batch's contiguous cache views (the dense
    fallback path): full logical views for paged layers, window-bounded
    rings for ring layers, the per-slot state rows as-is for state
    layers.

    bt: (B, max_blocks_per_seq) int32 physical block ids (trash-padded).
    """
    return _map_slots(cfg, lambda h, p: h.gather(cfg, p, bt), pages)


def scatter_token(cfg: ModelConfig, pages, views, bt: jax.Array,
                  pos: jax.Array):
    """Write what a decode step updated in the contiguous views back into
    the pool: the one new row for paged layers, the one ring row (with
    page-opening scrub) for ring layers, the whole per-slot state for
    state layers."""
    return _map_slots(
        cfg, lambda h, p, v: h.scatter(cfg, p, v, bt, pos), pages, views)


def write_prefill(cfg: ModelConfig, pages, caches, bt_row: jax.Array,
                  slot: jax.Array):
    """Scatter a freshly prefilled (batch=1, capacity=bucket) cache pytree
    into the pool.  ``bt_row``: block ids sized ``max(bucket /
    block_size, ring_blocks)`` — entries past the request's real block
    count point at the trash page.  ``slot``: the request's decode slot
    (receives the Mamba state rows)."""
    return _map_slots(
        cfg, lambda h, p, c: h.write_prefill(cfg, p, c, bt_row, slot),
        pages, caches)


def clone_block(cfg: ModelConfig, pages, src, dst, keep_tokens):
    """Copy-on-write clone: duplicate physical page ``src`` into ``dst``
    across every **paged**-kind leaf, keeping only the rows covering the
    first ``keep_tokens`` tokens and resetting the rest to the leaf's
    init fill value.  The scrub is what makes sharing safe: a shared tail
    page's rows past the matched prefix hold the *donor's* tokens (or its
    generated continuation), and — the PR 2 lesson — the pool never
    scrubs device memory on free, so without it stale rows would leak
    into the clone's owner.

    Ring/state leaves pass through untouched (the prefix cache is gated
    off for plans that have any); leaves with ``granularity > 1``
    (Quest's per-page stats) cannot keep a partial page soundly, so this
    raises at trace time if one is present with ``keep_tokens`` possibly
    nonzero — the cache policy page-aligns matches for such plans,
    making the CoW path unreachable.

    ``src``/``dst``/``keep_tokens`` are traced int32 scalars — one
    compile serves every clone.
    """
    def fn(h, p):
        if h.kind != "paged":
            return p
        leaves = h.spec(cfg).leaves
        out = {}
        for name, leaf in p.items():
            s = leaves[name]
            if s.granularity != 1:
                raise ValueError(
                    f"CoW clone of page-granular leaf {name!r} is unsound "
                    "(partial-page stats would cover scrubbed rows); the "
                    "prefix cache must page-align matches for this plan")
            page = leaf[src]                      # (KVH, rows, *suffix)
            row = jnp.arange(leaf.shape[2], dtype=jnp.int32)
            keepmask = (row < jnp.asarray(keep_tokens, jnp.int32)).reshape(
                (1, -1) + (1,) * len(s.suffix))
            page = jnp.where(keepmask, page,
                             jnp.asarray(s.fill, leaf.dtype))
            out[name] = leaf.at[dst].set(page)
        return out
    return _map_slots(cfg, fn, pages)


# -------------------------------------------------------------- accounting

def _leaf_row_bytes(s, cdt, kvh: int) -> float:
    """Bytes one *token* of leaf ``s`` occupies across ``kvh`` KV heads
    (row width x storage itemsize, amortized over the leaf's sequence
    granularity)."""
    return s.row_width(kvh) * jnp.dtype(s.leaf_dtype(cdt)).itemsize / \
        s.granularity


def kv_row_bytes(cfg: ModelConfig, names=("k", "v", "k_scale",
                                          "v_scale")) -> int:
    """Per-token K/V storage bytes across one KV head set — dtype-sized
    quantized payload plus the full-precision per-row scale leaves when
    the plan stores int8/fp8 pages."""
    spec = bk.kv_leaf_specs(cfg)
    cdt = jnp.dtype(cfg.compute_dtype)
    return int(sum(_leaf_row_bytes(spec[nm], cdt, cfg.num_kv_heads)
                   for nm in names if nm in spec))


def pool_block_bytes(cfg: ModelConfig) -> Dict[str, int]:
    """Bytes one physical pool block occupies, per layer **kind** and in
    total per block id (a block id addresses the same page in every
    paged/ring layer).  Sums every leaf of the resolved cache spec at its
    own storage dtype — int8/fp8 K/V pages, f32 scale rows, uint32 hash
    words, page-granular stats — so pool capacity math (bench residency,
    bytes/token reporting) tracks ``cfg.serving.kv_dtype``."""
    sv = cfg.serving
    cdt = jnp.dtype(cfg.compute_dtype)
    counts = cache_kind_counts(cfg)
    out = {"paged": 0, "ring": 0}
    for spec_l in cfg.layer_specs:
        plan = cfg.plan_for(spec_l)
        if plan.kind == "state":
            continue
        leaves = bk.layer_cache_spec(cfg, spec_l).leaves
        out[plan.kind] += int(sv.block_size * sum(
            _leaf_row_bytes(s, cdt, cfg.num_kv_heads)
            for s in leaves.values()))
    out["per_block_id"] = out["paged"] + out["ring"]
    out["num_paged_layers"] = counts["paged"]
    out["num_ring_layers"] = counts["ring"]
    return out


def cache_kind_counts(cfg: ModelConfig) -> Dict[str, int]:
    """Layer count per cache kind (``paged``/``ring``/``state``) under
    the per-layer plan — shared by the footprint model below and the
    serving observability layer's per-kind pool gauges."""
    counts = {"paged": 0, "ring": 0, "state": 0}
    for spec in cfg.layer_specs:
        counts[cfg.plan_for(spec).kind] += 1
    return counts


def gather_footprint(cfg: ModelConfig) -> Dict[str, int]:
    """Per-decode-step gathered bytes for the whole stack, full-view vs
    paged, broken down by layer kind (reported by
    ``benchmarks/bench_serving.py``).

    ``full_view_bytes_per_step``: every global-attention cache leaf
    materialized at ``(max_batch, KVH, max_context, ...)`` plus
    context-length K/V views for the window layers — what a plan-less
    pager would move.  ``paged_bytes_per_step``: global layers move
    metadata leaves plus the backend's ``selected_rows`` K/V rows (≈ 0
    with the fused paged kernel), window layers their *bounded* ring
    views (``window_bytes_per_step``), Mamba layers ≈ 0
    (``state_bytes_per_step`` reports the per-slot state size that moves
    through registers regardless — no gather, no growth with context).
    """
    sv = cfg.serving
    b, n = sv.max_batch, sv.max_context
    kvh = cfg.num_kv_heads
    cdt = jnp.dtype(cfg.compute_dtype)
    counts = cache_kind_counts(cfg)

    full = paged = window = 0
    selected = 0
    fused = False
    if counts["paged"]:
        backend = bk.get_backend(cfg.attention_backend)
        spec = backend.cache_spec(cfg)

        def leaf_bytes(s):
            return b * s.rows(n) * s.row_width(kvh) * jnp.dtype(
                s.leaf_dtype(cdt)).itemsize

        full_l = sum(leaf_bytes(s) for s in spec.values())
        # K/V storage leaves (quantized payload + its scale rows) are the
        # gather-on-demand set; metadata leaves stream in full
        kv_names = [nm for nm in ("k", "v", "k_scale", "v_scale")
                    if nm in spec]
        kv_bytes = sum(leaf_bytes(spec[nm]) for nm in kv_names)
        selected = backend.selected_rows(cfg, n)
        row_b = sum(_leaf_row_bytes(spec[nm], cdt, kvh)
                    for nm in kv_names)
        paged_l = (full_l - kv_bytes) + int(b * selected * row_b)
        fused = backend.supports_paged and backend.fused_paged(cfg)
        if fused:
            paged_l = 0
        if not backend.supports_paged:
            paged_l = full_l
        full += full_l * counts["paged"]
        paged += paged_l * counts["paged"]
    ring_fused = False
    if counts["ring"]:
        ring_rows = cfg.ring_geometry()[1]
        row_b = kv_row_bytes(cfg)       # dtype-sized K/V + scale rows
        ring_l = b * ring_rows * row_b
        full_l = b * n * row_b
        ring_fused = bool(cfg.use_ring_kernel)
        # the fused ring pass streams the circular page list in-kernel:
        # no XLA gather materializes the bounded window view
        window = 0 if ring_fused else ring_l * counts["ring"]
        full += full_l * counts["ring"]
        paged += window
    state = 0
    if counts["state"]:
        di, hd, st = cfg.d_inner, cfg.ssm_head_dim, cfg.ssm_state
        nh = cfg.ssm_heads
        conv_dim = di + 2 * st
        state_l = b * (nh * hd * st * 4 +
                       (cfg.ssm_conv_width - 1) * conv_dim * cdt.itemsize)
        state = state_l * counts["state"]

    return {
        "full_view_bytes_per_step": int(full),
        "paged_bytes_per_step": int(paged),
        "window_bytes_per_step": int(window),
        "state_bytes_per_step": int(state),
        "selected_rows": int(selected),
        "fused_paged_kernel": bool(fused),
        "fused_ring_kernel": bool(ring_fused),
        "num_paged_layers": counts["paged"],
        "num_ring_layers": counts["ring"],
        "num_state_layers": counts["state"],
    }
