"""GQA attention with pluggable sparse decode backends.

Training/prefill: dense causal attention (XLA einsum path — the Pallas
``flash_prefill`` kernel is the TPU fast path and is validated against the
same math in tests).  Local layers apply a sliding-window mask.

Chunked serving prefill (:func:`attention_prefill_chunk`) runs the same
dense math one ``prefill_chunk`` at a time directly against the engine's
page pool: the chunk's K/V + backend metadata are committed first, then
its queries attend causally over the paged logical view (prefix-extension
attention — the in-chunk causal mask composes with the context earlier
chunks committed; local layers compose the pre-write ring with in-chunk
K/V under the window mask).

Decode — the ``DecodeBackend`` / ``KVView`` contract
----------------------------------------------------

Global layers own no backend logic: every decode backend (``socket``,
``hard_lsh``, ``quest``, ``dense``, …) is one module in
:mod:`repro.models.backends` implementing the
:class:`~repro.models.backends.DecodeBackend` interface and registered
under its ``cfg.attention_backend`` name:

* ``cache_spec(cfg)``     — declarative leaf layout (trailing shape,
                            dtype, sequence granularity, init fill);
                            :func:`init_attention_cache` and
                            :func:`cache_logical_axes` derive from it.
* ``prefill_build(...)``  — prompt K/V rows + backend metadata into a
                            fresh contiguous cache.
* ``append(...)``         — one new token through a ``KVView``.
* ``attend(...)``         — decode attention against a ``KVView``.

A :class:`~repro.models.backends.KVView` hides cache layout:
``ContiguousView`` wraps the standard ``(B, KVH, N, ...)`` cache used by
the static/batch path; ``PagedView`` wraps the serving engine's page pool
plus a per-request block table (pass ``block_tables`` to
:func:`attention_decode`).  Backends whose ``attend`` touches K/V only
through indexed ``gather_rows`` (top-k selection) declare
``supports_paged`` — the serving engine then skips contiguous-view
materialization entirely and per decode step moves only the small
metadata leaves plus ``O(top_k)`` K/V rows.

**Adding a backend**: write one module under ``models/backends/``
implementing the four methods against the ``KVView`` API, register it in
``models/backends/__init__.py``, and it is reachable from training-free
decode, the static serve path and (if paged-capable) the continuous
engine, with sharding axes and paged-pool layout derived from its spec.

``pos`` may be a scalar (lockstep batch) or a ``(B,)`` vector of
per-request positions (ragged serving batch); backends derive per-request
sparsity budgets from the vector case.

Local (sliding-window) layers decode from a ring buffer of ``window``
slots — for gemma3's 5:1 pattern this keeps the long_500k cache bounded
by the window on 52 of 62 layers.  On the continuous
engine the ring lives in pool pages (cache-plan kind ``"ring"``): pass
``block_tables`` and the layer reads/writes through a
:class:`~repro.models.backends.RingView`, whose circular page list
bounds per-slot block demand at ``ceil(window / block_size)`` — same
attention math, recycled pages (``cfg.ring_geometry()``).
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.distributed import sharding as shd
from repro.distributed.sharding import lsc
from repro.models import backends
from repro.models import param as pm
from repro.models.backends import kvquant, socket_config_of
from repro.models.layers import apply_rope, init_rmsnorm, rmsnorm, softcap

__all__ = ["init_attention", "attention_train", "attention_prefill",
           "attention_prefill_chunk", "attention_decode",
           "init_attention_cache", "socket_config_of"]

NEG_INF = -1e30


def _eff_heads(cfg: ModelConfig) -> Tuple[int, int]:
    """(num_heads, num_kv_heads) after optional zero-padding for TP."""
    if not cfg.logical_pad_heads:
        return cfg.num_heads, cfg.num_kv_heads
    pad = 16

    def up(x):
        return ((x + pad - 1) // pad) * pad

    h = up(cfg.num_heads)
    kv = cfg.num_kv_heads
    while h % kv:  # keep exact grouping
        h += pad
    return h, kv


# ------------------------------------------------------------------ init

def init_attention(cfg: ModelConfig, rng: jax.Array) -> Dict:
    d, hd = cfg.d_model, cfg.head_dim
    h, kv = _eff_heads(cfg)
    k1, k2, k3, k4, k5 = jax.random.split(rng, 5)
    s = 1.0 / np.sqrt(d)
    so = 1.0 / np.sqrt(h * hd)
    dtype = jnp.dtype(cfg.param_dtype)
    params = {
        "wq": pm.normal(k1, (d, h, hd), ("embed_w", "heads", None),
                        stddev=s, dtype=dtype),
        "wk": pm.normal(k2, (d, kv, hd), ("embed_w", "kv_heads", None),
                        stddev=s, dtype=dtype),
        "wv": pm.normal(k3, (d, kv, hd), ("embed_w", "kv_heads", None),
                        stddev=s, dtype=dtype),
        "wo": pm.normal(k4, (h, hd, d), ("heads", None, "embed_w"),
                        stddev=so, dtype=dtype),
    }
    if cfg.logical_pad_heads and h != cfg.num_heads:
        # zero the padded q heads and their output rows => exact function.
        mask = (jnp.arange(h) < cfg.num_heads).astype(dtype)
        params["wq"].value = params["wq"].value * mask[None, :, None]
        params["wo"].value = params["wo"].value * mask[:, None, None]
    if cfg.qk_norm:
        params["q_norm"] = init_rmsnorm(hd)
        params["k_norm"] = init_rmsnorm(hd)
    # SOCKET hyperplanes (Algorithm 1): data-agnostic, never trained.
    sset = cfg.socket
    params["hash_w"] = pm.constant(
        jax.random.normal(k5, (sset.num_tables, sset.num_planes, hd),
                          jnp.float32),
        ("tables", None, None))
    return params


# ------------------------------------------------------------- projections

def _project_qkv(cfg: ModelConfig, params: Dict, x: jax.Array,
                 positions: jax.Array):
    cdt = jnp.dtype(cfg.compute_dtype)
    x = x.astype(cdt)
    q = jnp.einsum("btd,dhk->bthk", x, params["wq"].astype(cdt))
    k = jnp.einsum("btd,dhk->bthk", x, params["wk"].astype(cdt))
    v = jnp.einsum("btd,dhk->bthk", x, params["wv"].astype(cdt))
    if cfg.qk_norm:
        q = rmsnorm(params["q_norm"], q)
        k = rmsnorm(params["k_norm"], k)
    if cfg.use_rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _scale(cfg: ModelConfig) -> float:
    """The attention logits' scale: ``cfg.attention_multiplier``, else
    1/sqrt(head_dim)."""
    if cfg.attention_multiplier is None:
        return 1.0 / np.sqrt(cfg.head_dim)
    return cfg.attention_multiplier


def _merge_heads(cfg: ModelConfig, params: Dict, ctx: jax.Array) -> jax.Array:
    cdt = jnp.dtype(cfg.compute_dtype)
    return jnp.einsum("bthk,hkd->btd", ctx.astype(cdt),
                      params["wo"].astype(cdt))


# ------------------------------------------------------------------ train

def _use_repeat_kv(h_eff: int, kv: int) -> bool:
    """GQA sharding strategy: the grouped (kv, g) einsum
    layout cannot be sharded when kv_heads doesn't divide the model axis —
    XLA then replicates *all* heads and the (B,H,T,S) logits explode.
    Repeating K/V up to the flat q-head axis keeps 16-way head sharding at
    the cost of a cheap KV broadcast (k/v are tiny next to the logits)."""
    mesh = shd.current_mesh()
    if mesh is None:
        return False
    model = dict(mesh.shape).get("model", 1)
    return (kv % model != 0) and (h_eff % model == 0) and h_eff != kv


def _attn_chunk(cfg: ModelConfig, qg: jax.Array, k: jax.Array, v: jax.Array,
                q_offset, attn_type: str, scale: float,
                repeat_kv: bool) -> jax.Array:
    """Attention of a block of queries against the full K/V (exact,
    full-row softmax).

    grouped:   qg (B, cq, KV, G, hd); k/v (B, S, KV, hd)
    repeat_kv: qg (B, cq, H, hd);     k/v (B, S, H, hd)  (pre-repeated)
    """
    cq = qg.shape[1]
    s = k.shape[1]
    if repeat_kv:
        logits = jnp.einsum("bthd,bshd->bhts", qg.astype(jnp.float32),
                            k.astype(jnp.float32)) * scale
    else:
        logits = jnp.einsum("btkgd,bskd->bkgts", qg.astype(jnp.float32),
                            k.astype(jnp.float32)) * scale
    logits = softcap(logits, cfg.attn_logit_softcap)
    ti = q_offset + jnp.arange(cq)[:, None]
    si = jnp.arange(s)[None, :]
    mask = si <= ti
    if attn_type == "local":
        mask &= (ti - si) < cfg.sliding_window
    if repeat_kv:
        logits = jnp.where(mask[None, None], logits, NEG_INF)
        w = jax.nn.softmax(logits, axis=-1)
        return jnp.einsum("bhts,bshd->bthd", w, v.astype(jnp.float32))
    logits = jnp.where(mask[None, None, None], logits, NEG_INF)
    w = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bkgts,bskd->btkgd", w, v.astype(jnp.float32))


def attention_train(cfg: ModelConfig, params: Dict, x: jax.Array,
                    positions: jax.Array, attn_type: str) -> jax.Array:
    """Dense causal attention (optionally sliding-window) for training.

    x: (B, T, d); positions: (B, T).  When ``cfg.attn_q_chunk`` divides T,
    queries are processed in chunks under ``lax.scan`` so the live logits
    buffer is (chunk, T) instead of (T, T) — the XLA-path equivalent of the
    flash_prefill kernel's memory behaviour (exact same math).
    """
    b, t, d = x.shape
    h_eff = params["wq"].shape[1]
    kv = params["wk"].shape[1]
    g = h_eff // kv
    q, k, v = _project_qkv(cfg, params, x, positions)
    q = lsc(q, "batch", "seq", "q_heads", None)
    scale = _scale(cfg)

    repeat_kv = _use_repeat_kv(h_eff, kv)
    if repeat_kv:
        qg = q                                       # (b,t,h,hd)
        k = lsc(jnp.repeat(k, g, axis=2), "batch", "seq", "q_heads", None)
        v = lsc(jnp.repeat(v, g, axis=2), "batch", "seq", "q_heads", None)
    else:
        qg = q.reshape(b, t, kv, g, cfg.head_dim)

    cq = cfg.attn_q_chunk
    if cq and t > cq and t % cq == 0:
        nc = t // cq
        q_chunks = jnp.moveaxis(
            qg.reshape(b, nc, cq, *qg.shape[2:]), 1, 0)
        offsets = jnp.arange(nc, dtype=jnp.int32) * cq

        def body(_, inp):
            qc, off = inp
            return None, _attn_chunk(cfg, qc, k, v, off, attn_type, scale,
                                     repeat_kv)

        _, ctx_chunks = jax.lax.scan(body, None, (q_chunks, offsets))
        ctx = jnp.moveaxis(ctx_chunks, 0, 1)
    else:
        ctx = _attn_chunk(cfg, qg, k, v, 0, attn_type, scale, repeat_kv)
    ctx = ctx.reshape(b, t, h_eff, cfg.head_dim).astype(x.dtype)
    return _merge_heads(cfg, params, ctx)


# ------------------------------------------------------------------ cache

def init_attention_cache(cfg: ModelConfig, batch: int, capacity: int,
                         attn_type: str, dtype=None,
                         long_context: bool = False,
                         ring_capacity: Optional[int] = None) -> Dict:
    """Allocate one layer's decode cache (zeros); returns the pytree.

    ``long_context`` switches the sequence axis to context-parallel
    sharding (annotated logically; physical placement set by the launcher).
    ``ring_capacity`` overrides the local-layer ring length (the paged
    engine needs page-aligned rings, ``ring_blocks * block_size``, instead
    of the static path's ``min(capacity, window)``).
    """
    dtype = dtype or jnp.dtype(cfg.compute_dtype)
    _, kv = _eff_heads(cfg)
    if attn_type == "local":
        cap = ring_capacity if ring_capacity is not None else \
            min(capacity, cfg.sliding_window)
        # same leaf layout as the ring pool pages: quantized storage adds
        # the k_scale/v_scale leaves here too (kv_leaf_specs resolves
        # serving.kv_dtype)
        return {name: jnp.full((batch, kv, cap, *s.suffix), s.fill,
                               s.leaf_dtype(dtype))
                for name, s in backends.kv_leaf_specs(cfg).items()}
    backend = backends.get_backend(cfg.attention_backend)
    return backend.init_cache(cfg, batch, kv, capacity, dtype)


def cache_logical_axes(cfg: ModelConfig, attn_type: str,
                       long_context: bool = False) -> Dict:
    """Logical axis names mirroring :func:`init_attention_cache`."""
    if attn_type == "local":
        return {name: s.axes("cache_seq")
                for name, s in backends.kv_leaf_specs(cfg).items()}
    seq = "cache_seq_cp" if long_context else "cache_seq"
    return backends.get_backend(cfg.attention_backend).cache_axes(cfg, seq)


# ---------------------------------------------------------------- prefill

def attention_prefill(cfg: ModelConfig, params: Dict, x: jax.Array,
                      positions: jax.Array, attn_type: str,
                      capacity: int, last_index=None,
                      paged: bool = False) -> Tuple[jax.Array, Dict]:
    """Forward over the prompt + build this layer's decode cache.

    Output matches :func:`attention_train`; cache covers positions [0, T).

    ``last_index``: optional ``(B,)`` per-row last *real* positions for
    bucket-padded prompts — the local ring then keeps the window ending
    at ``last_index`` instead of the (padding-garbage) bucket end.
    ``paged``: build the local ring at the serving engine's page-aligned
    capacity (``cfg.ring_geometry()``) so it scatters 1:1 into pool pages.
    """
    b, t, _ = x.shape
    y = attention_train(cfg, params, x, positions, attn_type)
    q, k, v = _project_qkv(cfg, params, x, positions)  # recompute, cheap
    kc = jnp.swapaxes(k, 1, 2)   # (B,KV,T,hd)
    vc = jnp.swapaxes(v, 1, 2)
    if attn_type == "local":
        cap = cfg.ring_geometry()[1] if paged else \
            min(capacity, cfg.sliding_window)
        li = jnp.full((b,), t - 1, jnp.int32) if last_index is None else \
            jnp.asarray(last_index, jnp.int32)
        # ring slot s holds the newest kept position p ≡ s (mod cap); the
        # same formula the decode step uses to reconstruct slot positions
        sl = jnp.arange(cap, dtype=jnp.int32)
        ring_pos = li[:, None] - ((li[:, None] - sl[None]) % cap)  # (B,cap)
        valid = (ring_pos >= 0)[:, None, :, None]
        idx = jnp.clip(ring_pos, 0, t - 1)[:, None, :, None]
        ring_k = jnp.where(valid, jnp.take_along_axis(kc, idx, axis=2), 0)
        ring_v = jnp.where(valid, jnp.take_along_axis(vc, idx, axis=2), 0)
        kvd = backends.kv_quant_mode(cfg)
        if kvquant.is_quantized(kvd):
            kq, ks = kvquant.quantize(ring_k, kvd)
            vq, vs = kvquant.quantize(ring_v, kvd)
            return y, {"k": kq, "v": vq, "k_scale": ks, "v_scale": vs}
        if kvd == "bf16":
            ring_k = ring_k.astype(jnp.bfloat16)
            ring_v = ring_v.astype(jnp.bfloat16)
        cache = {"k": ring_k, "v": ring_v}
        return y, cache
    cache = init_attention_cache(cfg, b, capacity, attn_type,
                                 dtype=kc.dtype)
    backend = backends.get_backend(cfg.attention_backend)
    return y, backend.prefill_build(cfg, params, cache, kc, vc)


def attention_prefill_chunk(cfg: ModelConfig, params: Dict, x: jax.Array,
                            positions: jax.Array, attn_type: str,
                            cache: Dict, layer, bt_row: jax.Array,
                            history: jax.Array, last_index: jax.Array,
                            ) -> Tuple[jax.Array, Dict]:
    """One **prefix-extension** prefill chunk straight against the pool.

    The chunked engine feeds the prompt through the stack
    ``prefill_chunk`` tokens at a time; this is one attention layer's
    share of one chunk.  ``x`` is ``(1, C, d)`` (one chunk per engine
    iteration), ``positions`` the absolute token positions ``history +
    [0, C)``, ``cache`` the layer-stacked *pool* leaves this layer is
    index ``layer`` of (written in place), ``bt_row`` the
    request's trash-padded block-id row, ``history`` the number of
    prompt tokens already committed by earlier chunks (a traced scalar —
    one compile covers every chunk index), and ``last_index`` the
    ``(1,)`` last *real* in-chunk index (the final chunk is padded to the
    static chunk length).

    Global layers write the chunk's K/V + backend metadata into their
    pages first (reusing the backend's ``prefill_build`` on a chunk-sized
    mini cache), then attend causally over the paged logical view — the
    ``si <= ti`` mask composes in-chunk causality with the committed
    context, which is exactly the prefix-extension contract.  Local
    layers attend over the pre-write circular ring (history) plus the
    in-chunk K/V under the sliding-window mask, then write the chunk's
    real rows into the ring with the usual page-opening scrub; padded
    rows are routed to the trash page so ring slots only ever hold
    positions the decode-side ring arithmetic can reconstruct.
    """
    b, t, _ = x.shape
    hd = cfg.head_dim
    h_eff = params["wq"].shape[1]
    kv = params["wk"].shape[1]
    g = h_eff // kv
    scale = _scale(cfg)
    q, k, v = _project_qkv(cfg, params, x, positions)
    kc = jnp.swapaxes(k, 1, 2)                       # (B, KV, C, hd)
    vc = jnp.swapaxes(v, 1, 2)
    bs = cfg.serving.block_size
    cache = dict(cache)
    qg = q.reshape(b, t, kv, g, hd)
    li = jnp.asarray(last_index, jnp.int32).reshape(b)

    if attn_type == "local":
        rb, cap = cfg.ring_geometry()
        w = cfg.sliding_window
        # history ring as of position history-1: slot s holds the newest
        # committed position p ≡ s (mod cap); slots never written (or
        # fallen out of the window) mask out.  Gathered BEFORE the chunk
        # writes, so early chunk queries still see positions a later
        # in-chunk token will recycle.
        ring_k = backends.gather_block_leaf(cache["k"], bt_row[None, :rb],
                                            layer)
        ring_v = backends.gather_block_leaf(cache["v"], bt_row[None, :rb],
                                            layer)
        kvd = backends.kv_quant_mode(cfg)
        if kvquant.is_quantized(kvd):
            ring_k = kvquant.dequantize(ring_k, backends.gather_block_leaf(
                cache["k_scale"], bt_row[None, :rb], layer))
            ring_v = kvquant.dequantize(ring_v, backends.gather_block_leaf(
                cache["v_scale"], bt_row[None, :rb], layer))
        sl = jnp.arange(cap, dtype=jnp.int32)
        lp = jnp.asarray(history, jnp.int32) - 1
        rp = lp - ((lp - sl) % cap)                          # (cap,)
        ti = history + jnp.arange(t, dtype=jnp.int32)        # (t,)
        ring_mask = (rp[None, :] >= 0) & (ti[:, None] - rp[None, :] < w)
        ij = jnp.arange(t, dtype=jnp.int32)
        in_mask = (ij[None, :] <= ij[:, None]) & \
            (ij[:, None] - ij[None, :] < w)
        k_all = jnp.concatenate([ring_k, kc], axis=2)    # (B,KV,cap+C,hd)
        v_all = jnp.concatenate([ring_v, vc], axis=2)
        logits = jnp.einsum("btkgd,bknd->bkgtn", qg.astype(jnp.float32),
                            k_all.astype(jnp.float32)) * scale
        logits = softcap(logits, cfg.attn_logit_softcap)
        mask = jnp.concatenate([ring_mask, in_mask], axis=1)  # (t, cap+C)
        logits = jnp.where(mask[None, None, None], logits, NEG_INF)
        wts = jax.nn.softmax(logits, axis=-1)
        ctx = jnp.einsum("bkgtn,bknd->btkgd", wts,
                         v_all.astype(jnp.float32))
        ctx = ctx.reshape(b, t, h_eff, hd)

        def body(j, cc):
            pos = jnp.full((b,), history + j, jnp.int32)
            blk = bt_row[(pos // bs) % rb]
            # padded rows (j > last_index) go to the trash page (block 0)
            blk = jnp.where(j <= li, blk, jnp.zeros_like(blk))
            vals = {"k": kc[:, :, j], "v": vc[:, :, j]}
            if kvquant.is_quantized(kvd):
                vals["k"], vals["k_scale"] = kvquant.quantize(vals["k"], kvd)
                vals["v"], vals["v_scale"] = kvquant.quantize(vals["v"], kvd)
            return {name: backends.ring_write_page(
                cc[name], layer, blk, pos, vals[name], block_size=bs,
                ring_blocks=rb, window=w) for name in cc}

        ring_names = [n for n in ("k", "v", "k_scale", "v_scale")
                      if n in cache]
        ring_leaves = jax.lax.fori_loop(
            0, t, body, {n: cache[n] for n in ring_names})
        cache.update(ring_leaves)
    else:
        backend = backends.get_backend(cfg.attention_backend)
        # chunk-sized mini cache through the backend's own prefill_build:
        # K/V plus metadata (SOCKET bits/vnorm, Quest page stats) land in
        # the chunk's pages block-aligned (C % block_size == 0, and every
        # leaf granularity divides block_size by construction).
        mini = backend.init_cache(cfg, b, kv, t,
                                  jnp.dtype(cfg.compute_dtype))
        mini = backend.prefill_build(cfg, params, mini, kc, vc)
        block0 = jnp.asarray(history, jnp.int32) // bs
        spec = backend.cache_spec(cfg)
        # row-granular commit: supports a mid-page chunk start (prefix-
        # cache hit resuming past the shared tail page) and routes
        # final-chunk padding to the trash page.
        by_row = [name for name in cache if spec[name].granularity == 1]
        cache.update(backends.write_chunk_rows(
            {name: cache[name] for name in by_row}, layer,
            {name: mini[name] for name in by_row}, bt_row, history, li[0]))
        for name in cache:
            if spec[name].granularity != 1:
                # page-granular metadata (Quest min/max): whole-block
                # writes — chunk starts are page-aligned here (the
                # prefix cache only shares page-aligned prefixes when
                # any leaf has granularity > 1).
                cache[name] = backends.write_chunk_blocks(
                    cache[name], layer, mini[name], bt_row, block0)
        # prefix-extension attend over the paged logical view: the chunk's
        # own rows were just committed, so the causal si <= ti mask covers
        # both the earlier chunks' pages and in-chunk causality; trash
        # rows sit past every real query's position.
        k_full = backends.gather_block_leaf(cache["k"], bt_row[None], layer)
        v_full = backends.gather_block_leaf(cache["v"], bt_row[None], layer)
        if kvquant.is_quantized(backends.kv_quant_mode(cfg)):
            k_full = kvquant.dequantize(k_full, backends.gather_block_leaf(
                cache["k_scale"], bt_row[None], layer))
            v_full = kvquant.dequantize(v_full, backends.gather_block_leaf(
                cache["v_scale"], bt_row[None], layer))
        ctx = _attn_chunk(cfg, qg, jnp.swapaxes(k_full, 1, 2),
                          jnp.swapaxes(v_full, 1, 2), history, "global",
                          scale, repeat_kv=False)
        ctx = ctx.reshape(b, t, h_eff, hd)

    return _merge_heads(cfg, params, ctx.astype(x.dtype)), cache


# ----------------------------------------------------------------- decode

def attention_decode(cfg: ModelConfig, params: Dict, x: jax.Array,
                     cache: Dict, pos: jax.Array, attn_type: str,
                     layer, block_tables: Optional[jax.Array] = None,
                     ) -> Tuple[jax.Array, Dict]:
    """One decode step.  x: (B, 1, d); pos: scalar int32 (current index)
    OR a ``(B,)`` int32 vector of per-request indices (ragged serving
    batch — each row of the batch sits at its own context length).

    ``cache`` holds the layer-stacked leaves this layer is index
    ``layer`` of; the new token's rows are written in place.
    ``block_tables``: when given (``(B, blocks_per_seq)`` physical block
    ids), ``cache`` is the serving engine's **page pool** rather than a
    contiguous cache — the backend appends and attends through a
    :class:`~repro.models.backends.PagedView`, so paged-capable backends
    never materialize the full per-request K/V view.

    In the ragged case the sparse backends' top-k budget is applied *per
    request* from each live length (``k_r = clip(ceil(len_r / sparsity),
    min_k, k_cap)``) via dynamic masking under a static ``top_k`` — the
    serving-engine realization of the paper's ``k = N / sparsity``.

    Returns (y (B,1,d), updated cache/pool).
    """
    b = x.shape[0]
    hd = cfg.head_dim
    h_eff = params["wq"].shape[1]
    kv = params["wk"].shape[1]
    g = h_eff // kv
    scale = _scale(cfg)
    ragged = jnp.ndim(pos) == 1
    positions = jnp.reshape(pos, (b, 1)).astype(jnp.int32) if ragged \
        else jnp.full((b, 1), pos, jnp.int32)
    with jax.named_scope("layer.proj"):
        q, k_new, v_new = _project_qkv(cfg, params, x, positions)
    qg = jnp.transpose(q.reshape(b, 1, kv, g, hd), (0, 2, 3, 1, 4))
    # qg: (B, KV, G, 1, hd)

    if attn_type == "local":
        ring_fused = block_tables is not None and cfg.use_ring_kernel
        spec = backends.kv_leaf_specs(cfg)
        if block_tables is not None:
            # paged ring: the block table's first ring_blocks entries are
            # a circular page list (plan kind "ring"); the bounded ring
            # view (window-sized) then runs the same attention math.
            rb, cap = cfg.ring_geometry()
            view = backends.RingView(
                cache, spec, block_tables, cfg.serving.block_size, rb,
                cfg.sliding_window, layer=layer)
            backends.write_token_kv(cfg, view, pos, k_new[:, 0],
                                    v_new[:, 0])
        else:
            # static ring of cap slots: token pos lives at slot pos % cap
            cap = cache["k"].shape[3]
            view = backends.ContiguousView(cache, spec, layer=layer)
            backends.write_token_kv(cfg, view, pos % cap, k_new[:, 0],
                                    v_new[:, 0])
        if ring_fused:
            # fused Pallas ring pass: stream the circular page list
            # straight from the pool, window mask (and dequant, for
            # quantized pages) in-kernel — the leaf() gather below never
            # materializes.
            from repro.kernels.paged_attention import ops as pa_ops
            pages = view.layer_arrays()
            ctx = pa_ops.paged_ring_attend(
                qg, pages["k"], pages["v"], block_tables[:, :rb],
                pos=pos, window=cfg.sliding_window,
                softcap=cfg.attn_logit_softcap, scale=scale,
                k_scale=pages.get("k_scale"),
                v_scale=pages.get("v_scale"))
            backends.record_fused("paged_ring", ctx.shape)
        else:
            ring_k = backends.dequant_leaf(cfg, view, "k")
            ring_v = backends.dequant_leaf(cfg, view, "v")
            # ring-slot absolute positions; invalid slots masked out.  The
            # window bound is a no-op when cap <= window (static path) but
            # trims page-aligned rings that hold slightly more than a
            # window.
            sl = jnp.arange(cap, dtype=jnp.int32)
            pos_b = pos[:, None] if ragged else pos     # (B,1) | scalar
            ring_pos = pos_b - ((pos_b - sl) % cap)      # (B,cap) | (cap,)
            valid = (ring_pos >= 0) & \
                (pos_b - ring_pos < cfg.sliding_window)
            if not ragged:
                valid = valid[None]
            logits = jnp.einsum("bkgtd,bknd->bkgtn",
                                qg.astype(jnp.float32),
                                ring_k.astype(jnp.float32)) * scale
            logits = softcap(logits, cfg.attn_logit_softcap)
            logits = jnp.where(valid[:, None, None, None], logits, NEG_INF)
            w = jax.nn.softmax(logits, axis=-1)
            ctx = jnp.einsum("bkgtn,bknd->bkgtd", w,
                             ring_v.astype(jnp.float32))
    else:
        backend = backends.get_backend(cfg.attention_backend)
        spec = backend.cache_spec(cfg)
        if block_tables is None:
            view = backends.ContiguousView(cache, spec, layer=layer)
        else:
            view = backends.PagedView(cache, spec, block_tables,
                                      block_size=cfg.serving.block_size,
                                      layer=layer)
        backend.append(cfg, params, view, jnp.swapaxes(k_new, 1, 2),
                       jnp.swapaxes(v_new, 1, 2), pos)
        ctx = backend.attend(cfg, params, qg, view, length=pos + 1,
                             scale=scale)

    ctx = jnp.transpose(ctx, (0, 3, 1, 2, 4)).reshape(b, 1, h_eff, hd)
    with jax.named_scope("layer.proj"):
        return _merge_heads(cfg, params, ctx.astype(x.dtype)), view.arrays
