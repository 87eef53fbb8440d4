"""Ring-buffer cache handler for sliding-window ("local") attention layers.

Sliding-window layers only ever attend the last ``window`` tokens, so
their pages need not accumulate with context: the request block table's
first ``ring_blocks = ceil(window / block_size)`` entries are reused as a
**circular page list** (logical token ``t`` -> entry ``(t // block_size)
% ring_blocks``, row ``t % block_size``) and old pages are recycled in
place.  Per-slot block demand is bounded by ``ring_blocks`` regardless of
context length — on gemma3's 5:1 local:global pattern that bounds 52 of
62 layers by the window instead of the context.

Decode-side reads/writes go through :class:`~repro.models.backends.base
.RingView` (``models/attention.py``); this handler owns the pool-side
half: prefill scatter, the bounded contiguous ring views of the dense
fallback path, and the write-back of decode-updated ring rows.  Both
write paths **scrub at page-opening writes** (see
:func:`~repro.models.backends.base.ring_write_page`): recycled pool
blocks carry the previous owner's data and are never zeroed on device.
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.models.backends import base

__all__ = ["RingCacheHandler"]


class RingCacheHandler(base.LayerCacheHandler):
    kind = "ring"

    def spec(self, cfg) -> base.LayerCacheSpec:
        return base.LayerCacheSpec(kind="ring",
                                   leaves=base.kv_leaf_specs(cfg),
                                   ring_blocks=cfg.ring_geometry()[0])

    def write_prefill(self, cfg, pages, cache, bt_row, slot):
        """The prefill ring cache is already in flat ring layout
        (``ring_blocks * block_size`` rows, slot ``s`` = the newest prompt
        position ``p ≡ s (mod capacity)``), so each ring block scatters to
        exactly one physical page — entries past the request's allocated
        blocks are trash-padded and absorb the unreachable writes.  Every
        row of every touched page is written, which scrubs any previous
        owner's data by construction."""
        del slot
        # the ring cache has rb * block_size rows, so the generic block
        # scatter consumes exactly bt_row[:ring_blocks]
        return {name: base.write_block_prefill(p, cache[name], bt_row)
                for name, p in pages.items()}

    def gather(self, cfg, pages, bt):
        """Bounded contiguous ring views ``(B, KVH, ring_blocks *
        block_size, hd)`` — window-sized, never context-sized."""
        rb = cfg.ring_geometry()[0]
        return {name: base.gather_block_leaf(p, bt[:, :rb])
                for name, p in pages.items()}

    def scatter(self, cfg, pages, views, bt, pos):
        bs = cfg.serving.block_size
        rb, rows = cfg.ring_geometry()
        b = bt.shape[0]
        bidx = jnp.arange(b)
        blk = bt[bidx, (pos // bs) % rb]
        out = {}
        for name, p in pages.items():
            val = views[name][bidx, :, pos % rows]     # (B, KVH, *rest)
            out[name] = base.ring_write_page(
                p[None], 0, blk, pos, val, block_size=bs, ring_blocks=rb,
                window=cfg.sliding_window)[0]
        return out
