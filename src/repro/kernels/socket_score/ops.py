"""Jitted public wrapper for the SOCKET scoring kernel.

Accepts the model's natural layouts and flattens to the kernel's (BH, ...)
convention.  ``interpret=None`` (the default) compiles with Mosaic on a
TPU backend and interprets elsewhere
(:func:`repro.kernels.common.resolve_interpret`).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.common import resolve_interpret
from repro.kernels.socket_score.socket_score import (DEFAULT_BLOCK_N,
                                                     socket_score_pallas)


@functools.partial(jax.jit, static_argnames=("num_tables", "num_planes",
                                             "tau", "block_n", "interpret"))
def _score_flat(bits, u, vnorm, *, num_tables, num_planes, tau, block_n,
                interpret):
    return socket_score_pallas(bits, u, vnorm, num_tables=num_tables,
                               num_planes=num_planes, tau=tau,
                               block_n=block_n, interpret=interpret)


def socket_score(bits: jax.Array, u: jax.Array,
                 vnorm: Optional[jax.Array] = None, *, num_tables: int,
                 num_planes: int, tau: float,
                 block_n: int = DEFAULT_BLOCK_N,
                 interpret: Optional[bool] = None) -> jax.Array:
    """Score keys for one decode step.

    Shapes (model layout):
      bits  uint32 (B, KVH, N, W)  or (BH, N, W)
      u     f32    (B, KVH, G, L, P) or (BH, G, L, P)
      vnorm        (B, KVH, N) or (BH, N) or None

    Returns scores f32 matching the leading layout: (B, KVH, N) / (BH, N).
    """
    interpret = resolve_interpret(interpret)
    squeeze = False
    if bits.ndim == 4:
        b, kvh, n, w = bits.shape
        bits = bits.reshape(b * kvh, n, w)
        u = u.reshape(b * kvh, *u.shape[2:])
        if vnorm is not None:
            vnorm = vnorm.reshape(b * kvh, n)
        squeeze = (b, kvh)
    n = bits.shape[1]
    blk = min(block_n, n)
    while n % blk:
        blk //= 2
    out = _score_flat(bits, u, vnorm, num_tables=num_tables,
                      num_planes=num_planes, tau=float(tau), block_n=blk,
                      interpret=interpret)
    if squeeze:
        out = out.reshape(*squeeze, n)
    return out
