"""Quest decode backend (page min/max metadata + page top-k) [43].

The metadata leaves are **page-granular** (``granularity =
cfg.quest.page_size`` rows in the cache spec): in the serving engine's
pool each physical block carries ``block_size / page_size`` min/max rows,
so Quest no longer fakes contiguous stats tensors — its page table IS the
block pool.  ``page_size`` must divide ``ServingSettings.block_size``
(asserted here and at engine construction).

Paged-capable: page scoring reads only the small kmin/kmax leaves; K/V
are gathered only for the selected pages.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.baselines import quest as quest_mod
from repro.models.backends import base
from repro.models.backends import probe as bprobe
from repro.models.backends.base import KVView, LeafSpec

__all__ = ["QuestBackend"]


class QuestBackend(base.DecodeBackend):
    name = "quest"
    supports_paged = True

    @staticmethod
    def quest_config(cfg) -> quest_mod.QuestConfig:
        """Single source of truth for Quest knobs: page geometry from
        ``cfg.quest``, budget/sink/window shared with the SOCKET settings."""
        return quest_mod.QuestConfig(
            page_size=cfg.quest.page_size, sparsity=cfg.socket.sparsity,
            sink_tokens=cfg.socket.sink_tokens,
            window_tokens=cfg.socket.window_tokens,
            min_pages=cfg.quest.min_pages)

    # ---- layout ---------------------------------------------------------
    def cache_spec(self, cfg):
        ps = cfg.quest.page_size
        if cfg.serving.block_size % ps:
            raise ValueError(
                f"quest page_size {ps} must divide serving block_size "
                f"{cfg.serving.block_size} (one block = whole pages)")
        hd = cfg.head_dim
        spec = base.kv_leaf_specs(cfg)
        spec["kmin"] = LeafSpec(suffix=(hd,), granularity=ps, fill=np.inf)
        spec["kmax"] = LeafSpec(suffix=(hd,), granularity=ps, fill=-np.inf)
        return spec

    # ---- ops ------------------------------------------------------------
    def prefill_build(self, cfg, params, cache, kc, vc):
        del params
        cache = base.write_prefill_kv(cfg, cache, kc, vc)
        # page stats from the keys the attend phase will actually read
        # back (the quantization round trip under int8/fp8 storage), so
        # the min/max bounds stay sound — cfg.quest.stats_from_quantized,
        # enforced by ModelConfig.validate()
        keff = base.effective_keys(cfg, kc)
        b, kvh, t, hd = kc.shape
        ps = cfg.quest.page_size
        n_pages_t = -(-t // ps)
        pad = n_pages_t * ps - t
        kpad_min = jnp.pad(keff, ((0, 0), (0, 0), (0, pad), (0, 0)),
                           constant_values=np.inf)
        kpad_max = jnp.pad(keff, ((0, 0), (0, 0), (0, pad), (0, 0)),
                           constant_values=-np.inf)
        kmin = kpad_min.reshape(b, kvh, n_pages_t, ps, hd).min(axis=3)
        kmax = kpad_max.reshape(b, kvh, n_pages_t, ps, hd).max(axis=3)
        cache["kmin"] = cache["kmin"].at[:, :, :n_pages_t].set(
            kmin.astype(cache["kmin"].dtype))
        cache["kmax"] = cache["kmax"].at[:, :, :n_pages_t].set(
            kmax.astype(cache["kmax"].dtype))
        return cache

    def append(self, cfg, params, view: KVView, kc, vc, pos):
        del params
        base.write_token_kv(cfg, view, pos, kc[:, :, 0], vc[:, :, 0])
        # stats merge the key the attend phase will read back (the
        # quantization round trip under int8/fp8 storage)
        knew = base.effective_keys(cfg, kc)[:, :, 0]     # (B, KVH, hd)
        # A token opening a fresh page must *reset* the stats, not merge:
        # in the serving pool a decode-growth block may be a reused page
        # still carrying the previous owner's min/max (BlockPool never
        # scrubs device memory), and merging against stale bounds corrupts
        # page selection.  Page starts always coincide with block starts
        # (page_size | block_size), so resetting at pos % page_size == 0
        # covers every first write into a page.
        first = jnp.asarray(pos, jnp.int32) % cfg.quest.page_size == 0
        if first.ndim:
            first = first[:, None, None]                 # (B,1,1) ragged
        view.rmw_token(
            "kmin", pos, lambda old: jnp.where(
                first, knew.astype(old.dtype),
                jnp.minimum(old, knew.astype(old.dtype))))
        view.rmw_token(
            "kmax", pos, lambda old: jnp.where(
                first, knew.astype(old.dtype),
                jnp.maximum(old, knew.astype(old.dtype))))

    def _attend_fused(self, cfg, params, q, view, *, length, scale):
        """Fused paged path: one Pallas pass over the block table."""
        del params
        qcfg = self.quest_config(cfg)
        if view.block_size % 8:
            raise NotImplementedError(
                f"fused paged kernel needs block_size % 8 == 0 (f32 "
                f"sublane tiling), got {view.block_size}")
        n = view.n_tokens
        kp = quest_mod.page_budget(qcfg, n // qcfg.page_size, n)
        from repro.kernels.paged_attention import ops as pa_ops
        pages = view.layer_arrays()
        out = pa_ops.paged_quest_attend(
            q, pages["k"], pages["v"], pages["kmin"], pages["kmax"],
            view.block_table, length=length,
            page_budget=kp, page_size=qcfg.page_size, scale=scale,
            sink_tokens=qcfg.sink_tokens, window_tokens=qcfg.window_tokens,
            k_scale=base.kv_scales_of(pages, "k"),
            v_scale=base.kv_scales_of(pages, "v"))
        base.record_fused("paged_quest", out.shape)
        return out.astype(q.dtype)

    def attend(self, cfg, params, q, view: KVView, *, length, scale):
        # probe shadow steps keep the unfused route (the fused page
        # selection is pinned bitwise to select_tokens by the harness)
        if cfg.quest.use_paged_kernel and isinstance(view, base.PagedView) \
                and not bprobe.capturing():
            return self._attend_fused(cfg, params, q, view, length=length,
                                      scale=scale)
        del params
        qcfg = self.quest_config(cfg)
        state = quest_mod.QuestState(kmin=view.leaf("kmin"),
                                     kmax=view.leaf("kmax"))
        idx, sel_mask = quest_mod.select_tokens(
            qcfg, state, q, length=length, n=view.n_tokens)
        if bprobe.capturing():
            # QuestConfig carries the sink/window fields selection_stats
            # reads; budget is page-granular and folded into sel_mask, so
            # the reported budget is the static selection width
            bprobe.emit(bprobe.selection_stats(
                qcfg, q, base.dequant_leaf(cfg, view, "k"), None,
                idx, sel_mask, length=length, budget=None,
                static_k=idx.shape[-1], scale=scale))
        k_sel, v_sel = base.gather_kv_rows(cfg, view, idx)
        return base.subset_attention(cfg, q, k_sel, v_sel, sel_mask,
                                     scale=scale)

    # ---- accounting -----------------------------------------------------
    def selected_rows(self, cfg, n):
        qcfg = self.quest_config(cfg)
        n_pages = -(-n // qcfg.page_size)
        return quest_mod.page_budget(qcfg, n_pages, n) * qcfg.page_size

    def fused_paged(self, cfg):
        return bool(cfg.quest.use_paged_kernel)
