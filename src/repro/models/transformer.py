"""The decoder stack: heterogeneous blocks, scan-over-groups, train /
prefill / decode entry points.

A model is ``pattern × num_groups + remainder`` blocks (configs.base).  The
repeated pattern is executed under ``jax.lax.scan`` with group-stacked
parameters so the lowered HLO contains ONE copy of the pattern body
regardless of depth — essential for 48-62-layer architectures both for
compile time (single-core CPU here, and real TPU fleets) and HLO size.
Remat policy is applied to the scan body.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import LayerSpec, ModelConfig
from repro.distributed.sharding import lsc
from repro.models import attention as attn
from repro.models import mamba as mb
from repro.models import moe as moe_mod
from repro.models import param as pm
from repro.models.layers import (apply_mlp, embed_tokens, init_embedding,
                                 init_mlp, init_rmsnorm, lm_head, rmsnorm)

__all__ = ["init_model", "forward_train", "loss_and_metrics", "prefill",
           "prefill_chunk", "decode_step", "init_decode_caches",
           "decode_cache_axes", "model_flops_per_token"]


# ------------------------------------------------------------------ blocks

def _init_block(cfg: ModelConfig, rng: jax.Array, spec: LayerSpec) -> Dict:
    k_mix, k_mlp = jax.random.split(rng)
    params: Dict = {"norm_mix": init_rmsnorm(cfg.d_model)}
    if spec.kind == "attn":
        params["attn"] = attn.init_attention(cfg, k_mix)
    else:
        params["mamba"] = mb.init_mamba(cfg, k_mix)
    if spec.mlp == "dense":
        params["norm_mlp"] = init_rmsnorm(cfg.d_model)
        params["mlp"] = init_mlp(cfg, k_mlp)
    elif spec.mlp == "moe":
        params["norm_mlp"] = init_rmsnorm(cfg.d_model)
        params["moe"] = moe_mod.init_moe(cfg, k_mlp)
    return params


def _residual(cfg: ModelConfig, x: jax.Array, h: jax.Array) -> jax.Array:
    """x + residual_multiplier · h (the mixer's and the MLP's branch)."""
    if cfg.residual_multiplier != 1.0:
        h = h * jnp.asarray(cfg.residual_multiplier, h.dtype)
    return x + h


def _mlp_branch(cfg: ModelConfig, params: Dict, spec: LayerSpec,
                x: jax.Array, dropless: bool = False):
    """(the block's MLP output, or None where it has none; the MoE's aux
    losses, or None)."""
    if spec.mlp == "dense":
        return apply_mlp(cfg, params["mlp"],
                         rmsnorm(params["norm_mlp"], x)), None
    if spec.mlp == "moe":
        return moe_mod.apply_moe(cfg, params["moe"],
                                 rmsnorm(params["norm_mlp"], x),
                                 dropless=dropless)
    return None, None


def _block_train(cfg: ModelConfig, params: Dict, spec: LayerSpec,
                 x: jax.Array, positions: jax.Array):
    aux = {"moe_lb_loss": jnp.float32(0), "moe_z_loss": jnp.float32(0)}
    h = rmsnorm(params["norm_mix"], x)
    if spec.kind == "attn":
        h = attn.attention_train(cfg, params["attn"], h, positions,
                                 spec.attn_type)
    else:
        h = mb.mamba_train(cfg, params["mamba"], h)
    x = _residual(cfg, x, h)
    y, moe_aux = _mlp_branch(cfg, params, spec, x)
    if y is not None:
        x = _residual(cfg, x, y)
    if moe_aux is not None:
        aux = moe_aux
    return lsc(x, "batch", "act_seq", "embed"), aux


def _block_prefill(cfg: ModelConfig, params: Dict, spec: LayerSpec,
                   x: jax.Array, positions: jax.Array, capacity: int,
                   last_index=None, paged: bool = False):
    h = rmsnorm(params["norm_mix"], x)
    if spec.kind == "attn":
        h, cache = attn.attention_prefill(cfg, params["attn"], h, positions,
                                          spec.attn_type, capacity,
                                          last_index=last_index, paged=paged)
    else:
        h, cache = mb.mamba_train(cfg, params["mamba"], h,
                                  return_state=True, last_index=last_index)
    x = _residual(cfg, x, h)
    y, _ = _mlp_branch(cfg, params, spec, x)
    if y is not None:
        x = _residual(cfg, x, y)
    return lsc(x, "batch", "act_seq", "embed"), cache


def _block_prefill_chunk(cfg: ModelConfig, params: Dict, spec: LayerSpec,
                         x: jax.Array, positions: jax.Array, cache: Dict,
                         layer, bt_row: jax.Array, slot: jax.Array,
                         history: jax.Array, last_index: jax.Array):
    """One block's share of one prefill chunk, writing layer ``layer`` of
    the stacked pool leaves ``cache`` in place (see
    :func:`prefill_chunk`).  An MoE MLP dispatches dropless."""
    h = rmsnorm(params["norm_mix"], x)
    if spec.kind == "attn":
        h, cache = attn.attention_prefill_chunk(
            cfg, params["attn"], h, positions, spec.attn_type, cache,
            layer, bt_row, history, last_index)
    else:
        # Mamba state carries across chunks through the per-slot rows:
        # read the previous chunk's SSD state + conv tail, run the chunk
        # (padding past last_index is exact identity steps), write back.
        # The FIRST chunk starts from zeros — the slot row still holds the
        # previous occupant's state (nothing scrubs it on free).
        with jax.named_scope("mamba.state"):
            start = {name: (layer, slot) + (0,) * (a.ndim - 2)
                     for name, a in cache.items()}
            # one read of the slot's rows, which both the chunk's output
            # and its final state consume, so the write below updates
            # the pool in place
            old = jax.lax.cond(
                jnp.asarray(history, jnp.int32) == 0,
                lambda: {name: jnp.zeros((1,) + a.shape[2:], a.dtype)
                         for name, a in cache.items()},
                lambda: {name: jax.lax.dynamic_slice(
                    a, start[name], (1, 1) + a.shape[2:])[0]
                    for name, a in cache.items()})
        h, st = mb.mamba_train(cfg, params["mamba"], h, h0=old["ssm"],
                               conv0=old["conv"], return_state=True,
                               last_index=last_index)
        with jax.named_scope("mamba.state"):
            cache = {name: jax.lax.dynamic_update_slice(
                a, st[name].astype(a.dtype)[None], start[name])
                for name, a in cache.items()}
    x = _residual(cfg, x, h)
    y, _ = _mlp_branch(cfg, params, spec, x, dropless=True)
    if y is not None:
        x = _residual(cfg, x, y)
    return x, cache


def _block_decode(cfg: ModelConfig, params: Dict, spec: LayerSpec,
                  x: jax.Array, cache: Dict, pos: jax.Array, layer,
                  active: jax.Array, block_tables=None):
    """One block's decode step on layer ``layer`` of the stacked cache
    leaves ``cache``, written in place.  A Mamba layer takes its new
    state rows only where ``active`` (see :func:`decode_step`)."""
    with jax.named_scope("layer.proj"):
        h = rmsnorm(params["norm_mix"], x)
    if spec.kind == "attn":
        # the QKV/O projections and rope are scoped layer.proj inside
        h, cache = attn.attention_decode(cfg, params["attn"], h, cache, pos,
                                         spec.attn_type, layer,
                                         block_tables=block_tables)
    else:
        with jax.named_scope("mamba.state"):
            old = {name: jax.lax.dynamic_slice_in_dim(a, layer, 1)
                   for name, a in cache.items()}
        # scoped mamba.proj / mamba.scan inside
        h, new = mb.mamba_decode(cfg, params["mamba"], h,
                                 {name: a[0] for name, a in old.items()})
        with jax.named_scope("mamba.state"):
            cache = {name: jax.lax.dynamic_update_slice_in_dim(
                a, jnp.where(
                    active.reshape((1, -1) + (1,) * (a.ndim - 2)),
                    new[name][None].astype(a.dtype), old[name]), layer, 0)
                for name, a in cache.items()}
    x = _residual(cfg, x, h)
    with jax.named_scope("layer.mlp"):
        # an MoE's router, experts and shared expert are scoped moe.*
        y, _ = _mlp_branch(cfg, params, spec, x, dropless=True)
        if y is not None:
            x = _residual(cfg, x, y)
    return x, cache


def _block_cache(cfg: ModelConfig, spec: LayerSpec, batch: int,
                 capacity: int, long_context: bool, pool=None):
    """``pool`` (a ``ServingSettings``) switches to paged-pool layout:
    local-attention leaves become full ``block_size``-row pages (the ring
    handler addresses them circularly; no window truncation) and Mamba
    state is one row per decode slot instead of per block."""
    if spec.kind == "attn":
        ring_cap = pool.block_size if (
            pool is not None and spec.attn_type == "local") else None
        return attn.init_attention_cache(cfg, batch, capacity,
                                         spec.attn_type,
                                         long_context=long_context,
                                         ring_capacity=ring_cap)
    if pool is not None:
        return mb.init_mamba_cache(cfg, pool.max_batch)
    return mb.init_mamba_cache(cfg, batch)


def _block_cache_axes(cfg: ModelConfig, spec: LayerSpec, long_context: bool):
    if spec.kind == "attn":
        return attn.cache_logical_axes(cfg, spec.attn_type, long_context)
    return mb.mamba_cache_logical_axes()


# ------------------------------------------------------------------- model

def init_model(cfg: ModelConfig, rng: jax.Array):
    """Boxed parameter tree: {embed, groups, remainder, final_norm}."""
    k_emb, k_grp, k_rem = jax.random.split(rng, 3)
    params: Dict = {"embed": {}}
    emb = init_embedding(cfg, k_emb)
    if cfg.input_mode != "tokens":
        emb.pop("table", None)     # frontend stub supplies embeddings
    params["embed"] = emb

    group_trees = []
    for g in range(cfg.num_groups):
        kg = jax.random.fold_in(k_grp, g)
        tree = {}
        for i, spec in enumerate(cfg.pattern):
            tree[f"slot_{i}"] = _init_block(cfg, jax.random.fold_in(kg, i),
                                            spec)
        group_trees.append(tree)
    params["groups"] = pm.stack_boxed(group_trees)

    params["remainder"] = {
        f"slot_{i}": _init_block(cfg, jax.random.fold_in(k_rem, i), spec)
        for i, spec in enumerate(cfg.remainder)
    }
    params["final_norm"] = init_rmsnorm(cfg.d_model)
    return params


def _remat(cfg: ModelConfig, fn):
    # prevent_cse=False: we only ever remat inside lax.scan, where the loop
    # boundary already prevents CSE; True inserts barrier ops that XLA:CPU
    # handles by duplicating the saved carry stack in f32 (2.5x temps).
    if cfg.remat_policy == "none":
        return fn
    if cfg.remat_policy == "full":
        return jax.checkpoint(fn, prevent_cse=False,
                              policy=jax.checkpoint_policies.
                              nothing_saveable)
    if cfg.remat_policy == "dots":
        return jax.checkpoint(
            fn, prevent_cse=False,
            policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    raise ValueError(cfg.remat_policy)


def _input_embed(cfg: ModelConfig, params, batch: Dict) -> jax.Array:
    if cfg.input_mode == "tokens":
        return embed_tokens(cfg, params["embed"], batch["tokens"])
    return lsc(batch["embeds"].astype(jnp.dtype(cfg.compute_dtype)),
               "batch", "act_seq", "embed")


def forward_train(cfg: ModelConfig, params, batch: Dict):
    """Full forward.  batch: {tokens|embeds, (positions)} -> (logits, aux)."""
    x = _input_embed(cfg, params, batch)
    b, s, _ = x.shape
    positions = batch.get("positions")
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))

    def group_body(carry, gparams):
        x, lb, zl = carry
        for i, spec in enumerate(cfg.pattern):
            x, aux = _block_train(cfg, gparams[f"slot_{i}"], spec, x,
                                  positions)
            lb = lb + aux["moe_lb_loss"]
            zl = zl + aux["moe_z_loss"]
        # barrier: stops XLA from hoisting the backward pass's f32 upcast
        # of the saved carry into the forward loop (which would materialize
        # a duplicate f32 residual stack — observed 2.5x temp blowup).
        x = jax.lax.optimization_barrier(x)
        return (x, lb, zl), None

    body = _remat(cfg, group_body)
    (x, lb, zl), _ = jax.lax.scan(
        body, (x, jnp.float32(0), jnp.float32(0)), params["groups"])

    for i, spec in enumerate(cfg.remainder):
        x, aux = _block_train(cfg, params["remainder"][f"slot_{i}"], spec,
                              x, positions)
        lb = lb + aux["moe_lb_loss"]
        zl = zl + aux["moe_z_loss"]

    x = rmsnorm(params["final_norm"], x)
    logits = lm_head(cfg, params["embed"], x)
    n_moe = sum(1 for sp in cfg.layer_specs if sp.mlp == "moe") or 1
    return logits, {"moe_lb_loss": lb / n_moe, "moe_z_loss": zl / n_moe}


def loss_and_metrics(cfg: ModelConfig, params, batch: Dict,
                     lb_coef: float = 0.01):
    """Causal-LM loss.  batch[labels] (B,S) int32, -1 = padding."""
    logits, aux = forward_train(cfg, params, batch)
    labels = batch["labels"]
    v = logits.shape[-1]
    # mask out padded vocab entries
    if v > cfg.vocab_size:
        pad_mask = jnp.arange(v) >= cfg.vocab_size
        logits = jnp.where(pad_mask[None, None], -1e30, logits)
    valid = labels >= 0
    labels_safe = jnp.maximum(labels, 0)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    token_ll = jnp.take_along_axis(logp, labels_safe[..., None],
                                   axis=-1)[..., 0]
    denom = jnp.maximum(jnp.sum(valid), 1)
    ce = -jnp.sum(jnp.where(valid, token_ll, 0.0)) / denom
    loss = ce + lb_coef * aux["moe_lb_loss"] + aux["moe_z_loss"]
    metrics = {"loss": loss, "ce": ce, "tokens": denom,
               "moe_lb_loss": aux["moe_lb_loss"]}
    return loss, metrics


# ----------------------------------------------------------------- serving

def init_decode_caches(cfg: ModelConfig, batch: int, capacity: int,
                       long_context: bool = False, pool=None):
    """Cache pytree: {"groups": stacked-per-group, "remainder": {...}}.

    ``pool``: optional ``ServingSettings`` — build the serving engine's
    paged pool instead (``batch = num_blocks``, ``capacity = block_size``;
    see :func:`_block_cache` for the per-kind layout differences).
    """
    def one_group():
        return {f"slot_{i}": _block_cache(cfg, spec, batch, capacity,
                                          long_context, pool)
                for i, spec in enumerate(cfg.pattern)}

    groups = jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs), *[one_group()
                                     for _ in range(cfg.num_groups)]) \
        if cfg.num_groups > 1 else jax.tree_util.tree_map(
            lambda x: x[None], one_group())
    rem = {f"slot_{i}": _block_cache(cfg, spec, batch, capacity,
                                     long_context, pool)
           for i, spec in enumerate(cfg.remainder)}
    return {"groups": groups, "remainder": rem}


def decode_cache_axes(cfg: ModelConfig, long_context: bool = False):
    groups = {f"slot_{i}": jax.tree_util.tree_map(
        lambda ax: ("groups",) + tuple(ax) if isinstance(ax, tuple) else ax,
        _block_cache_axes(cfg, spec, long_context),
        is_leaf=lambda x: isinstance(x, tuple))
        for i, spec in enumerate(cfg.pattern)}
    rem = {f"slot_{i}": _block_cache_axes(cfg, spec, long_context)
           for i, spec in enumerate(cfg.remainder)}
    return {"groups": groups, "remainder": rem}


def prefill(cfg: ModelConfig, params, batch: Dict, capacity: int,
            last_index=None, paged: bool = False):
    """Process the prompt, returning (last-token logits, caches).

    ``last_index``: optional ``(B,)`` int32 of per-request last *real*
    prompt positions.  The serving engine pads prompts up to a static
    bucket length; without it the returned logits would belong to the
    padding garbage rather than each prompt's true final token — and the
    sliding-window rings / Mamba states would absorb the padding (both
    are built *at* ``last_index`` when it is given).

    ``paged``: build caches in the serving engine's pool geometry where
    it differs from the static one (page-aligned local rings).
    """
    x = _input_embed(cfg, params, batch)
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))

    def group_body(x, gparams):
        caches = {}
        for i, spec in enumerate(cfg.pattern):
            x, caches[f"slot_{i}"] = _block_prefill(
                cfg, gparams[f"slot_{i}"], spec, x, positions, capacity,
                last_index, paged)
        return x, caches

    x, group_caches = jax.lax.scan(group_body, x, params["groups"])

    rem_caches = {}
    for i, spec in enumerate(cfg.remainder):
        x, rem_caches[f"slot_{i}"] = _block_prefill(
            cfg, params["remainder"][f"slot_{i}"], spec, x, positions,
            capacity, last_index, paged)

    if last_index is None:
        x = x[:, -1:]
    else:
        x = x[jnp.arange(b), jnp.asarray(last_index, jnp.int32)][:, None]
    x = rmsnorm(params["final_norm"], x)
    logits = lm_head(cfg, params["embed"], x)
    return logits, {"groups": group_caches, "remainder": rem_caches}


def _layer_scan(cfg: ModelConfig, block, params, caches, x):
    """Run ``block(spec, block_params, x, stacked_cache, layer) -> (x,
    stacked_cache)`` over the stack, the pool-addressing form every
    serving step shares.

    The scan-stacked cache leaves ride in the scan's **carry** beside
    ``x`` and each block writes its layer's new rows into them in place;
    the scan's inputs are only the group parameters and the layer index.
    Passing the caches as the scan's ``xs`` and taking them back as its
    ``ys`` would cut each layer's slice out of the stack, copy it to
    write it, and write a second whole pool.  A remainder block's leaves
    get a layer axis of one so that it addresses them the same way."""
    def group_body(carry, xs):
        x, gcache = carry
        gparams, layer = xs
        gcache = dict(gcache)
        for i, spec in enumerate(cfg.pattern):
            x, gcache[f"slot_{i}"] = block(spec, gparams[f"slot_{i}"], x,
                                           gcache[f"slot_{i}"], layer)
        return (x, gcache), None

    (x, groups), _ = jax.lax.scan(
        group_body, (x, caches["groups"]),
        (params["groups"], jnp.arange(cfg.num_groups, dtype=jnp.int32)))

    rem = {}
    for i, spec in enumerate(cfg.remainder):
        one = jax.tree_util.tree_map(lambda a: a[None],
                                     caches["remainder"][f"slot_{i}"])
        x, one = block(spec, params["remainder"][f"slot_{i}"], x, one, 0)
        rem[f"slot_{i}"] = jax.tree_util.tree_map(lambda a: a[0], one)
    return x, {"groups": groups, "remainder": rem}


def prefill_chunk(cfg: ModelConfig, params, caches, tokens: jax.Array,
                  *, bt_row: jax.Array, slot: jax.Array,
                  history: jax.Array, last_index: jax.Array):
    """One prefix-extension prefill chunk for the whole stack, directly
    against the serving engine's page pool.

    ``tokens``: ``(1, C)`` chunk token ids (the final chunk zero-padded to
    the static chunk length); ``caches``: the pool pytree (pages written
    in place, chunk attention reads committed history through the block
    table); ``bt_row``: ``(max_blocks_per_seq + C/block_size,)``
    trash-padded block ids; ``slot``: the request's decode slot (carries
    Mamba state across chunks); ``history``: tokens committed by earlier
    chunks (traced — one compile for every chunk index); ``last_index``:
    ``(1,)`` last real in-chunk index.

    Returns ``(logits (1,1,V) at last_index, updated caches)`` — the
    logits are only meaningful on the final chunk, where ``history +
    last_index + 1 == len(prompt)``.
    """
    x = embed_tokens(cfg, params["embed"], tokens)
    b, c, _ = x.shape
    positions = (jnp.asarray(history, jnp.int32) +
                 jnp.arange(c, dtype=jnp.int32))[None]
    positions = jnp.broadcast_to(positions, (b, c))

    def block(spec, bparams, x, cache, layer):
        return _block_prefill_chunk(cfg, bparams, spec, x, positions, cache,
                                    layer, bt_row, slot, history,
                                    last_index)

    x, caches = _layer_scan(cfg, block, params, caches, x)
    li = jnp.asarray(last_index, jnp.int32).reshape(b)
    x = x[jnp.arange(b), li][:, None]
    x = rmsnorm(params["final_norm"], x)
    logits = lm_head(cfg, params["embed"], x)
    return logits, caches


def decode_step(cfg: ModelConfig, params, caches, inputs: jax.Array,
                pos: jax.Array, block_tables=None, active=None):
    """One token for the whole stack.

    inputs: (B, 1) token ids or (B, 1, d) embeddings; pos: scalar int32 or
    a ``(B,)`` vector of per-request positions (ragged serving batch — see
    :func:`repro.models.attention.attention_decode`).

    ``block_tables``: per-request ``(B, blocks_per_seq)`` physical block
    ids — when given, ``caches`` is the serving engine's page pool (leaves
    ``(num_blocks, KVH, block_size, ...)``, shared block ids across
    layers) and attention layers read/write it through ``PagedView``.
    ``active``: optional ``(B,)`` bool of the slots holding a runnable
    request (all, by default).  Mamba layers keep the other slots' state
    rows: the step integrates their trash tokens like any slot's, and a
    state row has no trash row to absorb them, while a request between
    its prefill chunks needs its state to survive the decode steps run
    meanwhile.  Attention rows of inactive slots land in the trash page.

    The caches are carried through the layer scan and written in place
    (:func:`_layer_scan`).  Returns (logits (B,1,V), updated caches).
    """
    if cfg.input_mode == "tokens":
        x = embed_tokens(cfg, params["embed"], inputs)
    else:
        x = inputs.astype(jnp.dtype(cfg.compute_dtype))

    b = x.shape[0]
    active = jnp.ones((b,), bool) if active is None else active

    def block(spec, bparams, x, cache, layer):
        return _block_decode(cfg, bparams, spec, x, cache, pos, layer,
                             active, block_tables)

    x, caches = _layer_scan(cfg, block, params, caches, x)
    with jax.named_scope("model.head"):
        x = rmsnorm(params["final_norm"], x)
        logits = lm_head(cfg, params["embed"], x)
    return logits, caches


# ------------------------------------------------------------- accounting

def model_flops_per_token(cfg: ModelConfig, seq_len: int,
                          training: bool = True) -> float:
    """MODEL_FLOPS: 6·N_active·D-style accounting (+ attention quadratic
    term), for the roofline's useful-compute ratio."""
    n_active = cfg.active_param_count()
    mult = 6.0 if training else 2.0
    flops = mult * n_active
    # attention score+value flops per token: 2 * 2 * H * hd * attended
    attended = 0.0
    for spec in cfg.layer_specs:
        if spec.kind != "attn":
            continue
        span = seq_len / 2 if spec.attn_type == "global" else min(
            cfg.sliding_window, seq_len / 2)
        attended += span
    flops += mult / 3 * 2 * 2 * cfg.num_heads * cfg.head_dim * attended * 3
    return flops
