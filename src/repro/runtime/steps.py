"""Jit-able step functions shared by the dry-run, the train loop and the
serve loop.

``make_train_step`` supports gradient accumulation (microbatching): the
global batch is split into ``accum`` microbatches scanned sequentially with
fp32 gradient accumulation in parameter-sharded buffers.  This is the
standard memory lever for the ≥100B assigned architectures — activation
temps scale with the microbatch, grads/optimizer stay FSDP-sharded — and
it is also where DP comm/compute overlap comes from (XLA overlaps the
reduce-scatter of microbatch i's grads with microbatch i+1's compute).
"""

from __future__ import annotations

import functools
from typing import Callable, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import transformer as tfm
from repro.optim import AdamWConfig, adamw_update

__all__ = ["make_train_step", "make_prefill_step",
           "make_chunk_prefill_step", "make_serve_step"]


def make_train_step(cfg: ModelConfig, ocfg: AdamWConfig,
                    accum: int = 1, grad_shardings=None) -> Callable:
    """(params, opt_state, batch) -> (params, opt_state, metrics).

    ``grad_shardings``: optional pytree of NamedShardings (usually the
    parameter shardings) pinning the fp32 accumulation carry — without it
    XLA is free to pick an arbitrary scan-carry layout and pay full
    replication reshards at the optimizer boundary (measured 4-10x temp
    blowups on the MoE giants).
    """
    def _pin(tree):
        if grad_shardings is None:
            return tree
        return jax.tree_util.tree_map(
            jax.lax.with_sharding_constraint, tree, grad_shardings)

    def loss_fn(params, batch):
        return tfm.loss_and_metrics(cfg, params, batch)

    def train_step(params, opt_state, batch):
        if accum == 1:
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch)
        else:
            micro = jax.tree_util.tree_map(
                lambda x: x.reshape(accum, x.shape[0] // accum,
                                    *x.shape[1:]), batch)

            def body(carry, mb):
                gacc, lacc = carry
                (l, _), g = jax.value_and_grad(loss_fn, has_aux=True)(
                    params, mb)
                gacc = _pin(jax.tree_util.tree_map(
                    lambda a, b: a + b.astype(a.dtype), gacc, g))
                return (gacc, lacc + l), None

            gzero = _pin(jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params))
            (gsum, lsum), _ = jax.lax.scan(body, (gzero, jnp.float32(0)),
                                           micro)
            grads = _pin(jax.tree_util.tree_map(lambda g: g / accum, gsum))
            loss = lsum / accum
            metrics = {"loss": loss}
        new_p, new_o, om = adamw_update(ocfg, grads, opt_state, params)
        metrics = {**metrics, **om}
        metrics["loss"] = loss
        return new_p, new_o, metrics

    return train_step


def make_prefill_step(cfg: ModelConfig, capacity: int,
                      bucketed: bool = False,
                      paged: bool = False) -> Callable:
    """``bucketed=True`` adds a ``last_index`` argument: the continuous
    engine pads prompts to a static bucket, so the last *real* token's
    position must be passed explicitly (see :func:`tfm.prefill`) — it
    also pins sliding-window rings and Mamba states to the prompt's true
    end.  ``paged=True`` builds caches in pool geometry (page-aligned
    rings)."""
    if bucketed:
        def prefill_bucketed(params, batch, last_index):
            return tfm.prefill(cfg, params, batch, capacity=capacity,
                               last_index=last_index, paged=paged)
        return prefill_bucketed

    def prefill_step(params, batch):
        return tfm.prefill(cfg, params, batch, capacity=capacity)
    return prefill_step


def make_chunk_prefill_step(cfg: ModelConfig) -> Callable:
    """(params, pages, tokens, bt_row, slot, history, last_index) ->
    (last-real-token logits, pages).  One prefix-extension prefill chunk
    straight against the paged pool (see :func:`tfm.prefill_chunk`):
    the chunked engine's half of the token-budget mixed step.  The chunk
    length is static (``tokens.shape[1]``); history/slot/last_index are
    traced, so ONE compile serves every chunk of every request."""
    def chunk_step(params, pages, tokens, bt_row, slot, history,
                   last_index):
        return tfm.prefill_chunk(cfg, params, pages, tokens,
                                 bt_row=bt_row, slot=slot, history=history,
                                 last_index=last_index)
    return chunk_step


def make_serve_step(cfg: ModelConfig) -> Callable:
    """(params, caches, inp, pos[, block_tables, active]) -> (logits,
    caches).  ``pos`` may be a scalar (static batch) or a ``(B,)`` vector
    (ragged continuous batch); ``block_tables`` switches ``caches`` to the
    paged pool; ``active`` marks the runnable slots (see
    :func:`tfm.decode_step`)."""
    def serve_step(params, caches, inp, pos, block_tables=None,
                   active=None):
        return tfm.decode_step(cfg, params, caches, inp, pos,
                               block_tables=block_tables, active=active)
    return serve_step
