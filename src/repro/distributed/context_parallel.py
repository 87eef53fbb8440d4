"""Context-parallel SOCKET decode: sequence-sharded KV + distributed merge.

For ``long_500k`` (batch=1, 524288-token cache) the batch axis cannot be
sharded, so the KV cache (and the SOCKET bit cache) shards its *sequence*
axis across devices.  This module is the explicit shard_map implementation
of one decode-attention step under that layout — the controlled alternative
to letting XLA's SPMD partitioner invent the schedule:

  1. every shard scores its local keys (packed bits -> factorized scores);
  2. local value-aware top-k_local (k_local = ceil(k / shards));
  3. exact local attention over the local selection with *unnormalized*
     online-softmax stats (m_i, l_i, o_i);
  4. one tiny all-gather of (m, l, o) triples + closed-form merge:
        m* = max m_i;  l* = Σ l_i e^{m_i - m*};  o* = Σ o_i e^{m_i - m*}/l*

Communication per step = shards x (G x hd + 2G) floats — independent of
context length (vs. all-gathering N scores: 2 MB+ per head at 500k).
The union of local top-ks is a superset-quality approximation of global
top-k: it differs from exact global top-k only when one shard holds more
than k_local of the true top-k (tests measure recall ≥ the paper's
operating regime; a two-round exact variant is an EXPERIMENTS.md §Perf
candidate).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import socket

__all__ = ["context_parallel_socket_attend", "merge_partials"]


def merge_partials(m: jax.Array, l: jax.Array, o: jax.Array,
                   axis_name) -> jax.Array:
    """Merge per-shard online-softmax partials along ``axis_name``.

    m: (..., 1) row max; l: (..., 1) normalizer; o: (..., hd) unnormalized
    value accumulation (already divided by local l — we re-multiply).

    Uses pmax + two psums (2·G·(hd+2) floats per step) instead of the
    gather-everything formulation (shards× more traffic) — §Perf
    iteration 1 on the decode cells.
    """
    m_star = jax.lax.pmax(m, axis_name)
    w = l * jnp.exp(m - m_star)
    l_star = jax.lax.psum(w, axis_name)
    o_star = jax.lax.psum(o * w, axis_name)
    return o_star / jnp.maximum(l_star, 1e-30)


def _local_attend(cfg: socket.SocketConfig, w_hash, q, k_loc, v_loc, bits,
                  vnorm, lo, global_length, k_budget, scale):
    """Score + top-k + *partial* attention over this shard's keys.

    q: (B,KVH,G,1,hd); k/v_loc: (B,KVH,Nl,hd); ``lo`` = global index of the
    shard's first row.  Sink/window forcing uses *global* positions, so
    only the shard holding the prefix forces sinks and only the shard
    holding ``length`` forces the trailing window.  Returns (m, l, o)
    partials: (B,KVH,G,1,1), (B,KVH,G,1,1), (B,KVH,G,1,hd).
    """
    n_loc = k_loc.shape[2]
    if cfg.selection == "pooled":
        u = socket.soft_hash_query(w_hash,
                                   jnp.mean(q[..., 0, :], axis=2))
        scores = socket.soft_scores_factorized(cfg, bits, u)  # (B,KVH,Nl)
    else:
        u = socket.soft_hash_query(w_hash, q[..., 0, :])
        scores = socket.soft_scores_factorized(
            cfg, bits[:, :, None], u)                  # (B,KVH,G,Nl)
        scores = jnp.sum(scores, axis=2)               # kvhead selection

    gpos = lo + jnp.arange(n_loc, dtype=jnp.int32)
    glen = jnp.asarray(global_length, jnp.int32)
    valid = gpos < glen
    forced = (gpos < cfg.sink_tokens) | (gpos >= glen - cfg.window_tokens)
    eff = scores.astype(jnp.float32) * vnorm.astype(jnp.float32)
    eff = jnp.where(forced, jnp.float32(np.finfo(np.float32).max), eff)
    eff = jnp.where(valid, eff, socket.NEG_INF)
    _, idx = jax.lax.top_k(eff, k_budget)
    idx = idx.astype(jnp.int32)
    sel_mask = jnp.take_along_axis(
        jnp.broadcast_to(valid, eff.shape), idx, axis=-1)
    k_sel = jnp.take_along_axis(k_loc, idx[..., None], axis=2)
    v_sel = jnp.take_along_axis(v_loc, idx[..., None], axis=2)
    logits = jnp.einsum("bhgtd,bhkd->bhgtk", q.astype(jnp.float32),
                        k_sel.astype(jnp.float32)) * scale
    logits = jnp.where(sel_mask[:, :, None, None, :], logits, -1e30)
    m = jnp.max(logits, axis=-1, keepdims=True)        # (B,KVH,G,1,1)
    p = jnp.exp(logits - m)
    p = jnp.where(sel_mask[:, :, None, None, :], p, 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum("bhgtk,bhkd->bhgtd", p, v_sel.astype(jnp.float32))
    o = o / jnp.maximum(l, 1e-30)
    return m, l, o


def _sub(axes):
    if not axes:
        return None
    return axes[0] if len(axes) == 1 else tuple(axes)


def context_parallel_socket_attend(
        cfg: socket.SocketConfig, mesh: Mesh, seq_axes: Tuple[str, ...],
        w_hash: jax.Array, q: jax.Array, k_cache: jax.Array,
        v_cache: jax.Array, bits: jax.Array, vnorm: jax.Array,
        *, length, scale: float,
        batch_axes: Tuple[str, ...] = ()) -> jax.Array:
    """SOCKET decode attention with the cache sequence axis sharded over
    ``seq_axes`` (e.g. ("data",) or ("model",) or ("data", "model")), and
    the batch axis optionally sharded over ``batch_axes``.

    Shapes (global): q (B,KVH,G,1,hd); k/v (B,KVH,N,hd);
    bits (B,KVH,N,W); vnorm (B,KVH,N).
    """
    n = k_cache.shape[2]
    seq_axes = tuple(a for a in seq_axes if a in mesh.shape)
    batch_axes = tuple(a for a in batch_axes if a in mesh.shape)
    shards = int(np.prod([mesh.shape[a] for a in seq_axes]))
    k_total = socket.topk_budget(cfg, n)
    k_local = max(cfg.min_k, -(-k_total // shards))
    axis = seq_axes[0] if len(seq_axes) == 1 else seq_axes
    bax = _sub(batch_axes)

    cache_spec = P(bax, None, axis, None)
    flat_spec = P(bax, None, axis)
    rep = P(bax, None, None, None, None)

    def body(q_l, k_l, v_l, bits_l, vnorm_l, length_l):
        # this shard covers global rows [lo, lo+Nl)
        if isinstance(axis, tuple):
            sizes = [mesh.shape[a] for a in axis]
            idx = jax.lax.axis_index(axis[0])
            for a in axis[1:]:
                idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
        else:
            idx = jax.lax.axis_index(axis)
        n_l = k_l.shape[2]
        lo = idx * n_l
        m, l, o = _local_attend(cfg, w_hash, q_l, k_l, v_l, bits_l,
                                vnorm_l, lo, length_l, k_local, scale)
        merged = merge_partials(m, l, o, axis)
        return merged.astype(q_l.dtype)

    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(rep, cache_spec, cache_spec, cache_spec, flat_spec, P()),
        out_specs=rep,
        check_vma=False,
    )
    return fn(q, k_cache.astype(q.dtype), v_cache.astype(q.dtype), bits,
              vnorm, jnp.asarray(length, jnp.int32))
