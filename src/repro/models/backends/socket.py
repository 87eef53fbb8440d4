"""SOCKET decode backend (the paper's technique, Algorithms 1-3).

Cache leaves: K/V plus the side-cache of packed hash bits and value norms
(Algorithm 1).  ``attend`` soft-hashes the query (Algorithm 2), scores
every cached key with the factorized soft-collision kernel — through the
Pallas scoring kernel wherever it compiles (a TPU) or
``cfg.socket.use_score_kernel`` asks for it, else in XLA — runs
value-aware top-k (Algorithm 3), and attends exactly over the selected
subset (``flash_decode`` when ``cfg.socket.use_flash_decode``).

Paged-capable: scoring reads only the bits/vnorm leaves (~64x smaller
than K/V at deployment settings), and K/V are touched only at the
``top_k ∪ sink ∪ window`` rows the selection returns — the serving engine
never materializes contiguous K/V views for this backend.

With ``cfg.socket.use_paged_kernel`` the whole PagedView pipeline runs
as ONE fused Pallas pass (``kernels/paged_attention``): the pool leaves
and block table go into the kernel verbatim, which streams pages once —
scoring bits in-register, radix-selecting the per-request budget
threshold, and folding the selected K/V rows into an online softmax —
so even the ``O(top_k)`` XLA row gathers disappear.  Contiguous callers
keep the socket_score + flash_decode pair.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import hashing
from repro.core import socket as sk
from repro.kernels import common as kcommon
from repro.models.backends import base
from repro.models.backends import probe as bprobe
from repro.models.backends.base import ContiguousView, KVView, LeafSpec

__all__ = ["SocketBackend", "socket_config_of"]

# The bits leaf keeps a token's KV heads side by side in one row (a
# key-major page, ``LeafSpec(per_head=False)``), each head's hash words
# padded to whole rows of the TPU's 128 lanes.  Key-major is the order the
# scoring kernel reads; and with a narrower minor axis the device's
# default layout puts the block axis in the lanes instead, in which a page
# is no longer contiguous: the compiler then relayouts the whole leaf into
# and out of every step that writes a page of it.
LANES = 128


def bits_width(scfg: sk.SocketConfig) -> int:
    """Hash words (packed ``uint32``) or sign bytes (``int8``) of one
    token: the leading part of the bits leaf's minor axis."""
    if scfg.bits_storage == "packed":
        return hashing.num_words(scfg.num_tables, scfg.num_planes)
    return scfg.num_tables * scfg.num_planes


def socket_config_of(cfg) -> sk.SocketConfig:
    """Map the model config's :class:`SocketSettings` to the scorer's
    :class:`~repro.core.socket.SocketConfig`."""
    s = cfg.socket
    return sk.SocketConfig(
        num_planes=s.num_planes, num_tables=s.num_tables, tau=s.tau,
        sparsity=s.sparsity, sink_tokens=s.sink_tokens,
        window_tokens=s.window_tokens, min_k=s.min_k,
        bits_storage=s.bits_storage, score_chunk=s.score_chunk,
        score_dtype=s.score_dtype, selection=s.selection)


class SocketBackend(base.DecodeBackend):
    name = "socket"
    supports_paged = True

    # ---- layout ---------------------------------------------------------
    def cache_spec(self, cfg):
        scfg = socket_config_of(cfg)
        spec = base.kv_leaf_specs(cfg)
        spec["bits"] = LeafSpec(
            suffix=(cfg.num_kv_heads,
                    -(-bits_width(scfg) // LANES) * LANES),
            dtype=jnp.uint32 if scfg.bits_storage == "packed" else jnp.int8,
            per_head=False)
        spec["vnorm"] = LeafSpec(suffix=(), dtype=jnp.bfloat16)
        return spec

    @staticmethod
    def _words(bits: jax.Array) -> jax.Array:
        """A bits leaf's logical view ``(B, 1, N, KVH, W_pad)`` as the
        per-head ``(B, KVH, N, W_pad)``, lane padding included."""
        return jnp.moveaxis(bits[:, 0], 2, 1)

    @classmethod
    def _bits(cls, scfg, bits: jax.Array) -> jax.Array:
        """A bits leaf's hash words ``(B, KVH, N, W)``."""
        return cls._words(bits)[..., :bits_width(scfg)]

    @staticmethod
    def _stored(bits: jax.Array, leaf: jax.Array) -> jax.Array:
        """Per-head hash words ``bits`` ``(B, KVH, T, W)`` as rows of the
        bits leaf ``leaf``: ``(B, 1, T, KVH, W_pad)``, zero-padded."""
        pad = leaf.shape[-1] - bits.shape[-1]
        rows = jnp.moveaxis(bits, 1, 2)
        return jnp.pad(rows, [(0, 0)] * (rows.ndim - 1) + [(0, pad)])[:, None]

    # ---- ops ------------------------------------------------------------
    def prefill_build(self, cfg, params, cache, kc, vc):
        t = kc.shape[2]
        cache = base.write_prefill_kv(cfg, cache, kc, vc)
        scfg = socket_config_of(cfg)
        side = sk.precompute_key_hashes(
            scfg, jax.lax.stop_gradient(params["hash_w"]), kc, vc)
        cache["bits"] = cache["bits"].at[:, :, :t].set(
            self._stored(side.bits, cache["bits"]))
        cache["vnorm"] = cache["vnorm"].at[:, :, :t].set(
            side.vnorm.astype(cache["vnorm"].dtype))
        return cache

    @jax.named_scope("socket.append")
    def append(self, cfg, params, view: KVView, kc, vc, pos):
        base.write_token_kv(cfg, view, pos, kc[:, :, 0], vc[:, :, 0])
        # side-cache from the ORIGINAL full-precision K/V: selection is
        # untouched by K/V quantization by construction
        scfg = socket_config_of(cfg)
        side = sk.precompute_key_hashes(scfg, params["hash_w"], kc, vc)
        view.write_token("bits", pos, self._stored(
            side.bits, view.arrays["bits"])[:, :, 0])
        view.write_token("vnorm", pos, side.vnorm[:, :, 0])

    def _budget(self, cfg, length, n):
        """Ragged per-request top-k budget (None for scalar length)."""
        if jnp.ndim(length) != 1:
            return None
        scfg = socket_config_of(cfg)
        return sk.dynamic_topk_budget(scfg, length,
                                      sk.topk_budget(scfg, n))

    @staticmethod
    @jax.named_scope("socket.hash")
    def _soft_hash(scfg, params, q):
        """Query soft-hash for the selection mode: pooled hashes the
        group-mean query once per KV head ((B,KVH,L,P) — G x less scoring
        work/memory, the TPU operating point of DESIGN.md §2), else each
        q head ((B,KVH,G,L,P))."""
        if scfg.selection == "pooled":
            return sk.soft_hash_query(params["hash_w"],
                                      jnp.mean(q[..., 0, :], axis=2))
        return sk.soft_hash_query(params["hash_w"], q[..., 0, :])

    @jax.named_scope("socket.score")
    def _scores(self, cfg, params, q, view: KVView, length):
        """(soft-hash u, collision scores) for the selection mode.

        kvhead/pooled scoring runs the Pallas kernel where it compiles
        (a TPU) on packed bits, and wherever ``use_score_kernel`` asks
        for it; elsewhere the XLA factorized scorer."""
        scfg = socket_config_of(cfg)
        u = self._soft_hash(scfg, params, q)
        leaf = view.leaf("bits")
        bits = self._bits(scfg, leaf)
        if cfg.socket.use_score_kernel and scfg.selection == "qhead":
            raise NotImplementedError(
                "the Pallas scoring kernel group-sums scores (kvhead "
                "selection); use the XLA path for per-q-head selection")
        if scfg.selection != "qhead" and (
                cfg.socket.use_score_kernel
                or (scfg.bits_storage == "packed"
                    and kcommon.compiles_with_mosaic())):
            # bits_storage='int8' streams the ±1 plane bytes directly (the
            # kernel skips the unpack; format inferred from the dtype)
            from repro.kernels.socket_score import ops as score_ops
            # kernel wants (B,KVH,G,L,P); pooled hashes once per KV head
            u_k = u[:, :, None] if scfg.selection == "pooled" else u
            # packed words go in with their lane padding, which the
            # kernel never reads: a slice would copy them
            scores = score_ops.socket_score(
                self._words(leaf) if scfg.bits_storage == "packed"
                else bits, u_k, vnorm=None, num_tables=scfg.num_tables,
                num_planes=scfg.num_planes, tau=scfg.tau,
                length=length)                         # (B,KVH,N), G-sum
            base.record_fused("socket_score", scores.shape)
        elif scfg.selection == "pooled":
            scores = sk.soft_scores_factorized(scfg, bits, u)  # (B,KVH,N)
        else:
            scores = sk.soft_scores_factorized(
                scfg, bits[:, :, None], u)                     # (B,KVH,G,N)
            if scfg.selection == "kvhead":
                # group-marginal collision mass: sum over the query group
                scores = jnp.sum(scores, axis=2)
        return scores

    @jax.named_scope("socket.fused")
    def _attend_fused(self, cfg, params, q, view, *, length, scale, budget):
        """Fused paged path: one Pallas pass over the block table."""
        scfg = socket_config_of(cfg)
        if scfg.bits_storage != "packed":
            raise NotImplementedError(
                "the fused paged kernel streams packed uint32 hash words; "
                "bits_storage='int8' must use the unfused paged path")
        if scfg.selection not in ("kvhead", "pooled"):
            raise NotImplementedError(
                "the fused paged kernel group-sums scores (kvhead/pooled "
                "selection); per-q-head selection has no fused path")
        if view.block_size % 8:
            raise NotImplementedError(
                f"fused paged kernel needs block_size % 8 == 0 (f32 "
                f"sublane tiling), got {view.block_size}")
        u = self._soft_hash(scfg, params, q)
        if scfg.selection == "pooled":
            u = u[:, :, None]                       # (B,KVH,1,L,P)
        kq = sk.topk_budget(scfg, view.n_tokens)
        if budget is None:
            budget = jnp.full((q.shape[0],), kq, jnp.int32)
        from repro.kernels.paged_attention import ops as pa_ops
        pages = view.layer_arrays()
        out = pa_ops.paged_socket_attend(
            q, pages["k"], pages["v"], self._bits(scfg, pages["bits"]),
            pages["vnorm"], u,
            view.block_table, length=length,
            budget=budget, num_tables=scfg.num_tables,
            num_planes=scfg.num_planes, tau=scfg.tau, scale=scale,
            sink_tokens=scfg.sink_tokens, window_tokens=scfg.window_tokens,
            k_scale=base.kv_scales_of(pages, "k"),
            v_scale=base.kv_scales_of(pages, "v"))
        base.record_fused("paged_attention", out.shape)
        return out.astype(q.dtype)

    def attend(self, cfg, params, q, view: KVView, *, length, scale):
        scfg = socket_config_of(cfg)
        if scfg.selection not in ("kvhead", "pooled", "qhead"):
            raise ValueError(scfg.selection)
        n = view.n_tokens
        budget = self._budget(cfg, length, n)

        # Probe shadow steps take the unfused XLA route even when the
        # fused kernel is on: the fused pass never materializes its
        # selection, and it is pinned elsewhere (differential harness)
        # to match value_aware_topk exactly — so the XLA selection
        # probed below IS the fused kernel's selection.
        if cfg.socket.use_paged_kernel and isinstance(view, base.PagedView) \
                and not bprobe.capturing():
            return self._attend_fused(cfg, params, q, view, length=length,
                                      scale=scale, budget=budget)

        mesh = None
        if isinstance(view, ContiguousView) and cfg.decode_cp_axes:
            from repro.distributed import sharding as shd
            mesh = shd.current_mesh()
            if mesh is not None and not any(a in mesh.shape
                                            for a in cfg.decode_cp_axes):
                mesh = None
        if mesh is not None:
            if jnp.ndim(length) == 1:
                # the shard_map fast path merges per-shard top-k under a
                # single scalar length; ragged batches take the pjit/XLA
                # route instead of crashing mid-serve
                from repro.serving.obs import warn_once
                warn_once(
                    "socket-ragged-cp-fallback",
                    "ragged decode + context-parallel SOCKET has no "
                    "shard_map path yet; falling back to the pjit/XLA "
                    "path for this step (scalar-length decode keeps the "
                    "context-parallel fast path)")
                mesh = None
        if mesh is not None:
            # §Perf: shard_map context-parallel path — local top-k per
            # sequence shard + psum online-softmax merge; avoids
            # materializing the (B,KVH,N) global score tensor
            from repro.distributed.context_parallel import \
                context_parallel_socket_attend
            return context_parallel_socket_attend(
                scfg, mesh, cfg.decode_cp_axes, params["hash_w"], q,
                base.dequant_leaf(cfg, view, "k"),
                base.dequant_leaf(cfg, view, "v"),
                self._bits(scfg, view.leaf("bits")),
                view.leaf("vnorm").astype(jnp.float32),
                length=length, scale=scale,
                batch_axes=cfg.decode_cp_batch_axes)

        scores = self._scores(cfg, params, q, view, length)
        kq = sk.topk_budget(scfg, n)
        if scfg.selection in ("kvhead", "pooled"):
            with jax.named_scope("socket.select"):
                vnorm = view.leaf("vnorm").astype(jnp.float32)
                idx, sel_mask = sk.value_aware_topk(
                    scfg, scores, vnorm, k=kq, length=length, n_total=n,
                    budget=budget)
            if bprobe.capturing():
                # probe reference reads the DEQUANTIZED cached keys — the
                # same values the attend phase sees, so recall measures
                # selection quality at the serving precision
                bprobe.emit(bprobe.selection_stats(
                    scfg, q, base.dequant_leaf(cfg, view, "k"), vnorm,
                    idx, sel_mask, length=length, budget=budget,
                    static_k=kq, scale=scale))
            with jax.named_scope("socket.gather"):
                k_sel, v_sel = base.gather_kv_rows(cfg, view, idx)
            with jax.named_scope("socket.attend"):
                return base.subset_attention(cfg, q, k_sel, v_sel,
                                             sel_mask, scale=scale)
        # per-q-head selection: fold G into the selection axis, gather per
        # (kvh, g).  More faithful to the paper's single-head exposition
        # but loses the shared KV gather (and the flash_decode layout).
        with jax.named_scope("socket.select"):
            vnorm = view.leaf("vnorm").astype(jnp.float32)
            idx, sel_mask = sk.value_aware_topk(
                scfg, scores, vnorm[:, :, None], k=kq, length=length,
                n_total=n, budget=budget)
        with jax.named_scope("socket.gather"):
            # (B,KVH,G,K,hd)
            k_sel, v_sel = base.gather_kv_rows(cfg, view, idx)
        with jax.named_scope("socket.attend"):
            logits = jnp.einsum("bhgtd,bhgkd->bhgtk", q.astype(jnp.float32),
                                k_sel.astype(jnp.float32)) * scale
            logits = jnp.where(sel_mask[:, :, :, None, :], logits,
                               sk.NEG_INF)
            wts = jax.nn.softmax(logits, axis=-1)
            out = jnp.einsum("bhgtk,bhgkd->bhgtd", wts,
                             v_sel.astype(jnp.float32))
        return out.astype(q.dtype)

    # ---- accounting -----------------------------------------------------
    def selected_rows(self, cfg, n):
        return sk.topk_budget(socket_config_of(cfg), n)

    def fused_paged(self, cfg):
        return bool(cfg.socket.use_paged_kernel)
