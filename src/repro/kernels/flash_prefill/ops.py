"""Jitted wrapper for the causal flash-attention prefill kernel."""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.common import resolve_interpret
from repro.kernels.flash_prefill.flash_prefill import (DEFAULT_BLOCK_K,
                                                       DEFAULT_BLOCK_Q,
                                                       flash_prefill_pallas)


@functools.partial(jax.jit, static_argnames=("scale", "window", "block_q",
                                             "block_k", "interpret"))
def _prefill_flat(q, k, v, *, scale, window, block_q, block_k, interpret):
    return flash_prefill_pallas(q, k, v, scale=scale, window=window,
                                block_q=block_q, block_k=block_k,
                                interpret=interpret)


def flash_prefill(q: jax.Array, k: jax.Array, v: jax.Array, *, scale: float,
                  window: int = 0, block_q: int = DEFAULT_BLOCK_Q,
                  block_k: int = DEFAULT_BLOCK_K,
                  interpret: Optional[bool] = None) -> jax.Array:
    """Causal attention.  q/k/v (BH, S, hd); returns f32 (BH, S, hd)."""
    interpret = resolve_interpret(interpret)
    s = q.shape[1]
    bq = min(block_q, s)
    bk = min(block_k, s)
    while s % bq:
        bq //= 2
    while s % bk:
        bk //= 2
    return _prefill_flat(q, k, v, scale=float(scale), window=int(window),
                         block_q=bq, block_k=bk, interpret=interpret)
