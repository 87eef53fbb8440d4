"""Hard-LSH decode backend (tau -> 0 ablation of SOCKET).

Shares SOCKET's cache layout (packed sign bits + value norms) and
value-aware top-k, but scores by *hard* collision counting: a key scores
the number of tables whose every plane sign agrees with the query's.
Paged-capable for the same reason SOCKET is — scoring reads only the bits
leaf, K/V only at the selected rows.

With ``cfg.socket.use_paged_kernel`` (the same gate as SOCKET — the
backends share the cache layout and every other kernel-eligibility
constraint) PagedView decode runs as one fused Pallas pass
(``kernels/paged_attention.paged_hard_lsh_attend``): in-register
bit-unpack + hard collision counting into the VMEM score ring, exact
radix-select of the per-request budget, and an online-softmax rescan of
only the selected rows — zero XLA gathers on the K/V pool.
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.core import hashing
from repro.core import socket as sk
from repro.models.backends import base
from repro.models.backends import probe as bprobe
from repro.models.backends.socket import SocketBackend, socket_config_of

__all__ = ["HardLSHBackend"]


def _hard_collision_scores(scfg: sk.SocketConfig, bits, u_signs):
    """Hard collision counts from the same packed bits.

    bits (B,KVH,N,W); u_signs (B,KVH,G,L,P) ±1.  Returns (B,KVH,G,N).
    """
    l, p = scfg.num_tables, scfg.num_planes
    k_signs = hashing.unpack_signs(bits, l, p)           # (B,KVH,N,L,P)
    agree = jnp.einsum("bknlp,bkglp->bkgnl", k_signs, u_signs)
    return jnp.sum((agree >= p).astype(jnp.float32), axis=-1)


class HardLSHBackend(SocketBackend):
    name = "hard_lsh"
    supports_paged = True

    def _attend_fused(self, cfg, params, q, view, *, length, scale, budget):
        """Fused paged path: one Pallas pass over the block table."""
        scfg = socket_config_of(cfg)
        if scfg.bits_storage != "packed":
            raise NotImplementedError(
                "the fused paged kernel streams packed uint32 hash words; "
                "bits_storage='int8' must use the unfused paged path")
        if view.block_size % 8:
            raise NotImplementedError(
                f"fused paged kernel needs block_size % 8 == 0 (f32 "
                f"sublane tiling), got {view.block_size}")
        u = sk.soft_hash_query(params["hash_w"], q[..., 0, :])
        u_signs = jnp.where(u >= 0, 1.0, -1.0)
        kq = sk.topk_budget(scfg, view.n_tokens)
        if budget is None:
            budget = jnp.full((q.shape[0],), kq, jnp.int32)
        from repro.kernels.paged_attention import ops as pa_ops
        pages = view.layer_arrays()
        out = pa_ops.paged_hard_lsh_attend(
            q, pages["k"], pages["v"], self._bits(scfg, pages["bits"]),
            pages["vnorm"], u_signs, view.block_table, length=length,
            budget=budget, num_tables=scfg.num_tables,
            num_planes=scfg.num_planes, scale=scale,
            sink_tokens=scfg.sink_tokens, window_tokens=scfg.window_tokens,
            k_scale=base.kv_scales_of(pages, "k"),
            v_scale=base.kv_scales_of(pages, "v"))
        base.record_fused("paged_hard_lsh", out.shape)
        return out.astype(q.dtype)

    def attend(self, cfg, params, q, view, *, length, scale):
        scfg = socket_config_of(cfg)
        n = view.n_tokens
        budget = self._budget(cfg, length, n)

        # probe shadow steps keep the unfused route (same reasoning as
        # SocketBackend.attend: the fused selection is pinned to
        # value_aware_topk by the differential harness)
        if cfg.socket.use_paged_kernel and isinstance(view, base.PagedView) \
                and not bprobe.capturing():
            return self._attend_fused(cfg, params, q, view, length=length,
                                      scale=scale, budget=budget)

        u = sk.soft_hash_query(params["hash_w"], q[..., 0, :])
        u_signs = jnp.where(u >= 0, 1.0, -1.0)
        scores = _hard_collision_scores(
            scfg, self._bits(scfg, view.leaf("bits")), u_signs)
        scores = jnp.sum(scores, axis=2)                 # sum over group
        kq = sk.topk_budget(scfg, n)
        vnorm = view.leaf("vnorm").astype(jnp.float32)
        idx, sel_mask = sk.value_aware_topk(
            scfg, scores, vnorm, k=kq,
            length=length, n_total=n, budget=budget)
        if bprobe.capturing():
            bprobe.emit(bprobe.selection_stats(
                scfg, q, base.dequant_leaf(cfg, view, "k"), vnorm,
                idx, sel_mask, length=length, budget=budget,
                static_k=kq, scale=scale))
        k_sel, v_sel = base.gather_kv_rows(cfg, view, idx)
        return base.subset_attention(cfg, q, k_sel, v_sel, sel_mask,
                                     scale=scale)

    def fused_paged(self, cfg):
        return bool(cfg.socket.use_paged_kernel)
