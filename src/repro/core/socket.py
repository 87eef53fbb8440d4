"""SOCKET: soft collision kernel estimation for sparse attention.

Implements Algorithms 1-3 of the paper:

* :func:`precompute_key_hashes`   — Algorithm 1 (prefill-time index build).
* :func:`soft_hash_query`         — Algorithm 2 (query soft hashing).
* :func:`soft_scores_factorized`  — the production scoring path (exact
  algebraic rewrite of eq. (3); see DESIGN.md §2).
* :func:`soft_scores_gather`      — the paper's literal LUT-gather
  formulation (oracle; used for tests and GPU-parity checks).
* :func:`value_aware_topk`        — Algorithm 3 selection (value-norm
  weighted, with sink + local-window union).
* :func:`sparse_attention_over_subset` — exact softmax attention over the
  selected subset (Algorithm 3 lines 6-7).
* :func:`socket_attend`           — the full decode-time composition.

Shapes use the cache layout ``(B, KVH, S, ...)``; queries are
``(B, KVH, G, qlen, hd)`` where ``G`` is the GQA group size (q heads per
KV head).  Everything is jit/pjit-friendly (static shapes; masking instead
of dynamic slicing).
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import hashing

__all__ = [
    "SocketConfig",
    "SocketCache",
    "precompute_key_hashes",
    "soft_hash_query",
    "log_normalizer",
    "bucket_probs_explicit",
    "soft_scores_gather",
    "soft_scores_factorized",
    "value_aware_topk",
    "per_batch",
    "sparse_attention_over_subset",
    "socket_attend",
    "topk_budget",
    "dynamic_topk_budget",
]

NEG_INF = -1e30


@dataclasses.dataclass(frozen=True)
class SocketConfig:
    """Hyper-parameters of the SOCKET scorer (paper Table 13 defaults)."""

    num_planes: int = 10          # P
    num_tables: int = 60          # L
    tau: float = 0.5              # soft-hash temperature
    sparsity: float = 10.0        # N / k  (k = budget)
    sink_tokens: int = 128        # always-attended prefix tokens
    window_tokens: int = 128      # always-attended local window
    min_k: int = 16               # floor for the top-k budget
    selection: str = "kvhead"     # "kvhead" | "qhead" (DESIGN.md §7.4)
    bits_storage: str = "packed"  # "packed" (uint32 words) | "int8" (±1)
    score_dtype: str = "float32"
    # XLA-path scoring chunk (keys per scan step); bounds the live unpacked
    # sign buffer at long context (0 = unchunked).  The Pallas kernel
    # streams blocks natively and ignores this.
    score_chunk: int = 0

    @property
    def hash_params(self) -> hashing.HashParams:
        return hashing.HashParams(self.num_planes, self.num_tables)

    @property
    def bits_per_token(self) -> int:
        return self.num_planes * self.num_tables

    def replace(self, **kw) -> "SocketConfig":
        return dataclasses.replace(self, **kw)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class SocketCache:
    """Per-layer SOCKET side-cache living next to the KV cache.

    ``bits``   — ``uint32 (B, KVH, S, W)`` packed sign bits (or
                 ``int8 (B, KVH, S, L*P)`` when ``bits_storage == 'int8'``).
    ``vnorm``  — ``(B, KVH, S)`` value L2 norms (bf16 in deployment).
    """

    bits: jax.Array
    vnorm: jax.Array

    def tree_flatten(self):
        return (self.bits, self.vnorm), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        del aux
        return cls(*children)


def topk_budget(cfg: SocketConfig, n: int) -> int:
    """Selection budget k for a context of length n (static python int).

    Floored at the forced sink+window count: those tokens are *always*
    attended (paper §6), so a budget smaller than their count would
    silently evict the recency window (forced ties sort by index, keeping
    only the prefix sinks) — at deployment settings (sink=window=128,
    sparsity=10) that used to happen for every context under 2560 tokens.
    """
    forced = min(n, cfg.sink_tokens + cfg.window_tokens)
    k = max(cfg.min_k, forced, int(np.ceil(n / cfg.sparsity)))
    return min(k, n)


def dynamic_topk_budget(cfg: SocketConfig, length: jax.Array,
                        cap: int) -> jax.Array:
    """Traced per-request budget for a ragged batch: ``ceil(len/sparsity)``
    with the same ``min_k`` and forced sink+window floors as
    :func:`topk_budget`, clamped to the static selection size ``cap``
    (``cap = topk_budget(cfg, n_view)`` guarantees the floors fit)."""
    length = jnp.asarray(length, jnp.int32)
    forced = jnp.minimum(length, cfg.sink_tokens + cfg.window_tokens)
    k = jnp.maximum(
        jnp.ceil(length.astype(jnp.float32) /
                 cfg.sparsity).astype(jnp.int32), forced)
    return jnp.clip(k, cfg.min_k, cap)


# ---------------------------------------------------------------------------
# Algorithm 1 — prefill
# ---------------------------------------------------------------------------

def precompute_key_hashes(cfg: SocketConfig, w: jax.Array, keys: jax.Array,
                          values: jax.Array) -> SocketCache:
    """Build the SOCKET side-cache for freshly computed keys/values.

    Args:
      w:      ``(L, P, d)`` hyperplanes (per layer; data-agnostic).
      keys:   ``(B, KVH, S, d)``.
      values: ``(B, KVH, S, d)``.
    """
    signs = hashing.hash_keys_signs(w, keys)          # (B,KVH,S,L,P) bool
    if cfg.bits_storage == "packed":
        bits = hashing.pack_signs(signs)              # (B,KVH,S,W) uint32
    elif cfg.bits_storage == "int8":
        bits = (signs.astype(jnp.int8) * 2 - 1).reshape(
            *signs.shape[:-2], cfg.num_tables * cfg.num_planes)
    else:
        raise ValueError(cfg.bits_storage)
    vnorm = jnp.linalg.norm(values.astype(jnp.float32), axis=-1)
    return SocketCache(bits=bits, vnorm=vnorm.astype(jnp.bfloat16))


# ---------------------------------------------------------------------------
# Algorithm 2 — query soft hashing
# ---------------------------------------------------------------------------

def soft_hash_query(w: jax.Array, q: jax.Array) -> jax.Array:
    """``u^(l) = tanh(W^(l) q) / sqrt(d)`` — Algorithm 2 line 3.

    Args:
      w: ``(L, P, d)``; q: ``(..., d)``.

    Returns:
      ``(..., L, P)`` float32.
    """
    d = q.shape[-1]
    proj = jnp.einsum("...d,lpd->...lp", q.astype(jnp.float32),
                      w.astype(jnp.float32))
    return jnp.tanh(proj) / jnp.sqrt(jnp.float32(d))


def log_normalizer(u: jax.Array, tau: float) -> jax.Array:
    """``log Z^(l) = sum_i log(2 cosh(u_i / tau))`` (DESIGN.md §2).

    Numerically stable form: ``log(2cosh(x)) = |x| + log1p(exp(-2|x|))``.
    """
    x = u / tau
    ax = jnp.abs(x)
    return jnp.sum(ax + jnp.log1p(jnp.exp(-2.0 * ax)), axis=-1)


def bucket_probs_explicit(u: jax.Array, tau: float) -> jax.Array:
    """Explicit softmax over all ``R = 2**P`` corners (Algorithm 2 lines 4-7).

    O(L * 2^P) memory — oracle/GPU-parity path only.

    Args:
      u: ``(..., L, P)``.
    Returns:
      ``(..., L, R)`` probabilities.
    """
    p = u.shape[-1]
    corners = jnp.asarray(hashing.hypercube_corners(p))   # (R, P)
    logits = jnp.einsum("...lp,rp->...lr", u, corners) / tau
    return jax.nn.softmax(logits, axis=-1)


# ---------------------------------------------------------------------------
# Scoring — eq. (3), two equivalent forms
# ---------------------------------------------------------------------------

def soft_scores_gather(bucket_ids: jax.Array, probs: jax.Array) -> jax.Array:
    """Paper-literal scoring: gather each key's bucket probability per table.

    Args:
      bucket_ids: ``(..., N, L)`` int32 in [0, R).
      probs:      ``(..., L, R)`` soft bucket distribution for the query.

    Returns:
      ``(..., N)`` soft collision scores  ``s_soft = sum_l p(b_j^l | q)``.
    """
    picked = jnp.take_along_axis(
        probs[..., None, :, :],                       # (...,1,L,R)
        bucket_ids[..., :, :, None],                  # (...,N,L,1)
        axis=-1,
    )[..., 0]                                         # (...,N,L)
    return jnp.sum(picked, axis=-1)


def _score_block(cfg: SocketConfig, bits: jax.Array, u: jax.Array,
                 logz: jax.Array) -> jax.Array:
    l, p = cfg.num_tables, cfg.num_planes
    sdt = jnp.dtype(cfg.score_dtype)   # bf16 halves the unpacked-sign
    # buffer at long context; fp32 (default) is exact for small tau
    if cfg.bits_storage == "packed":
        signs = hashing.unpack_signs(bits, l, p, dtype=sdt)
    else:
        signs = bits.reshape(*bits.shape[:-1], l, p).astype(sdt)
    logits = jnp.einsum("...nlp,...lp->...nl", signs,
                        u.astype(sdt),
                        preferred_element_type=jnp.float32) / cfg.tau
    z = jnp.exp(logits - logz[..., None, :])          # (..., N, L)
    return jnp.sum(z, axis=-1)


def soft_scores_factorized(cfg: SocketConfig, bits: jax.Array,
                           u: jax.Array) -> jax.Array:
    """Production scoring path — exact rewrite of the corner softmax.

    ``score_j = sum_l exp( (S_j^(l) . u^(l)) / tau  -  logZ^(l) )``

    where ``S`` are the stored ±1 sign bits.  This replaces the GPU gather
    with a dense ±1 contraction (DESIGN.md §2).  The Pallas kernel
    (kernels/socket_score) computes the same scores in one pass over the
    packed bits and serves kvhead/pooled decode on a TPU; this jnp
    version is the XLA path everywhere else.

    When ``cfg.score_chunk`` divides N, keys are scored under ``lax.scan``
    in chunks so the live unpacked-sign buffer stays bounded at long
    context (scores are per-key independent, so chunking is exact).

    Args:
      bits: packed ``uint32 (..., N, W)`` or int8 ``(..., N, L*P)``.
      u:    ``(..., L, P)`` query soft hash (see :func:`soft_hash_query`).

    Returns:
      ``(..., N)`` float32 scores (identical to :func:`soft_scores_gather`).
    """
    logz = log_normalizer(u, cfg.tau)                 # (..., L)
    n = bits.shape[-2]
    c = cfg.score_chunk
    if c and n > c and n % c == 0:
        nc = n // c
        blocks = jnp.moveaxis(
            bits.reshape(*bits.shape[:-2], nc, c, bits.shape[-1]), -3, 0)

        def body(_, blk):
            return None, _score_block(cfg, blk, u, logz)

        _, out = jax.lax.scan(body, None, blocks)     # (nc, ..., c)
        moved = jnp.moveaxis(out, 0, -2)              # (..., nc, c)
        return moved.reshape(*moved.shape[:-2], n)
    return _score_block(cfg, bits, u, logz)


# ---------------------------------------------------------------------------
# Algorithm 3 — value-aware top-k selection + exact attention on the subset
# ---------------------------------------------------------------------------

def per_batch(x: jax.Array, ndim: int) -> jax.Array:
    """Reshape a ``(B,)`` per-request scalar (e.g. a ragged batch's length
    vector) so it broadcasts against a ``(B, ..., N)`` tensor of rank
    ``ndim``; scalars pass through unchanged."""
    if x.ndim == 1:
        return x.reshape(x.shape[0], *([1] * (ndim - 1)))
    return x


def value_aware_topk(cfg: SocketConfig, scores: jax.Array, vnorm: jax.Array,
                     *, k: int, length: jax.Array | int,
                     n_total: int,
                     budget: Optional[jax.Array] = None,
                     ) -> Tuple[jax.Array, jax.Array]:
    """Select indices of the k keys with largest ``score * ||v||``.

    Sink tokens (prefix) and the trailing local window are force-included by
    overriding their effective score to +inf (standard practice in the
    sparse-attention literature; paper §6 keeps 128 sink+window tokens).
    Invalid (not-yet-written) cache slots are masked to -inf.

    Args:
      scores: ``(..., N)`` soft collision scores.
      vnorm:  ``(..., N)`` value norms.
      k:      static selection budget (includes sink/window).
      length: current valid context length — dynamic scalar, int, or a
              ``(B,)`` vector of per-request lengths (ragged serving batch).
      n_total: static cache capacity N.
      budget: optional dynamic per-request budget ``(B,)`` (or scalar)
              ≤ ``k``; selections ranked past it are masked out.  This is
              how the serving engine applies the paper's ``k = N/sparsity``
              with N = each request's *live* context length while keeping
              the top-k shape static.  Forced sink/window tokens sort
              first (+inf), so they survive any budget ≥ their count.

    Returns:
      (indices ``(..., k)`` int32, validity mask ``(..., k)`` bool).
    """
    pos = jnp.arange(n_total, dtype=jnp.int32)
    length = per_batch(jnp.asarray(length, jnp.int32), scores.ndim)
    valid = pos < length
    eff = scores.astype(jnp.float32) * vnorm.astype(jnp.float32)
    forced = (pos < cfg.sink_tokens) | (pos >= length - cfg.window_tokens)
    eff = jnp.where(forced, jnp.float32(np.finfo(np.float32).max), eff)
    eff = jnp.where(valid, eff, NEG_INF)
    top_vals, top_idx = jax.lax.top_k(eff, k)
    mask = top_vals > NEG_INF / 2
    if budget is not None:
        budget = per_batch(jnp.asarray(budget, jnp.int32), scores.ndim)
        mask = mask & (jnp.arange(k, dtype=jnp.int32) < budget)
    return top_idx.astype(jnp.int32), mask


def sparse_attention_over_subset(q: jax.Array, k_sel: jax.Array,
                                 v_sel: jax.Array, sel_mask: jax.Array,
                                 *, scale: float) -> jax.Array:
    """Exact softmax attention restricted to the selected subset (eq. (2)).

    Args:
      q:      ``(B, KVH, G, T, hd)``  (T = query length, 1 for decode).
      k_sel:  ``(B, KVH, K, hd)`` gathered keys.
      v_sel:  ``(B, KVH, K, hd)`` gathered values.
      sel_mask: ``(B, KVH, K)`` bool validity of each selected slot.
    Returns:
      ``(B, KVH, G, T, hd)``.
    """
    logits = jnp.einsum("bhgtd,bhkd->bhgtk", q.astype(jnp.float32),
                        k_sel.astype(jnp.float32)) * scale
    logits = jnp.where(sel_mask[:, :, None, None, :], logits, NEG_INF)
    w = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgtk,bhkd->bhgtd", w, v_sel.astype(jnp.float32))
    return out.astype(q.dtype)


def socket_attend(cfg: SocketConfig, w_hash: jax.Array, q: jax.Array,
                  k_cache: jax.Array, v_cache: jax.Array,
                  side: SocketCache, *, length: jax.Array | int,
                  scale: Optional[float] = None,
                  use_kernel: bool = False,
                  budget: Optional[jax.Array] = None) -> jax.Array:
    """Full SOCKET decode attention (Algorithms 2+3) for one new query step.

    Args:
      w_hash:  ``(L, P, d)`` hyperplanes for this layer.
      q:       ``(B, KVH, G, 1, hd)`` query (GQA grouped layout).
      k_cache: ``(B, KVH, N, hd)``; v_cache same.
      side:    SocketCache with bits ``(B, KVH, N, W)`` and vnorm.
      length:  valid prefix length of the cache (scalar or ``(B,)`` for a
               ragged serving batch).
      use_kernel: route scoring through the Pallas kernel (TPU path).
      budget: optional dynamic per-request top-k budget (see
              :func:`value_aware_topk`).

    Returns:
      attention output ``(B, KVH, G, 1, hd)``.
    """
    b, kvh, g, t, hd = q.shape
    n = k_cache.shape[2]
    scale = scale if scale is not None else 1.0 / np.sqrt(hd)
    kq = topk_budget(cfg, n)

    # --- Algorithm 2: soft-hash the query heads --------------------------
    if cfg.selection == "pooled":
        # TPU operating point (DESIGN.md §2): one soft-hash per KV head
        # from the group-mean query — G x less scoring work/memory
        u = soft_hash_query(w_hash, jnp.mean(q[..., 0, :], axis=2))
    else:
        u = soft_hash_query(w_hash, q[..., 0, :])      # (B,KVH,G,L,P)

    # --- scoring (factorized form; optionally the Pallas kernel) --------
    if use_kernel:
        if cfg.selection not in ("kvhead", "pooled"):
            raise NotImplementedError(
                "the Pallas scoring kernel group-sums scores (kvhead "
                "selection); use the XLA path for per-q-head selection")
        from repro.kernels.socket_score import ops as score_ops
        scores = score_ops.socket_score(
            side.bits, u, vnorm=None, num_tables=cfg.num_tables,
            num_planes=cfg.num_planes, tau=cfg.tau)    # (B,KVH,N) (G-summed)
    elif cfg.selection == "pooled":
        scores = soft_scores_factorized(cfg, side.bits, u)  # (B,KVH,N)
    else:
        bits = side.bits[:, :, None]                   # (B,KVH,1,N,·)
        scores = soft_scores_factorized(cfg, bits, u)  # (B,KVH,G,N)

    if cfg.selection in ("kvhead", "pooled"):
        # group-marginal collision mass: sum over the query group's heads.
        if not use_kernel and cfg.selection == "kvhead":
            scores = jnp.sum(scores, axis=2)           # (B,KVH,N)
    elif cfg.selection == "qhead":
        # per-q-head selection: fold G into the head axis for selection,
        # then attention must gather per (kvh, g).  More faithful to the
        # paper's single-head exposition but loses the shared KV gather.
        pass
    else:
        raise ValueError(cfg.selection)

    vnorm = side.vnorm.astype(jnp.float32)
    if cfg.selection in ("kvhead", "pooled"):
        idx, sel_mask = value_aware_topk(
            cfg, scores, vnorm, k=kq, length=length, n_total=n,
            budget=budget)
        k_sel = jnp.take_along_axis(k_cache, idx[..., None], axis=2)
        v_sel = jnp.take_along_axis(v_cache, idx[..., None], axis=2)
        return sparse_attention_over_subset(q, k_sel, v_sel, sel_mask,
                                            scale=scale)

    # per-q-head route
    idx, sel_mask = value_aware_topk(
        cfg, scores, vnorm[:, :, None], k=kq, length=length, n_total=n,
        budget=budget)
    k_sel = jnp.take_along_axis(k_cache[:, :, None], idx[..., None], axis=3)
    v_sel = jnp.take_along_axis(v_cache[:, :, None], idx[..., None], axis=3)
    logits = jnp.einsum("bhgtd,bhgkd->bhgtk", q.astype(jnp.float32),
                        k_sel.astype(jnp.float32)) * scale
    logits = jnp.where(sel_mask[:, :, :, None, :], logits, NEG_INF)
    wts = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgtk,bhgkd->bhgtd", wts, v_sel.astype(jnp.float32))
    return out.astype(q.dtype)
