"""Jitted public wrapper for the SOCKET scoring kernel.

Accepts the model's natural layouts and turns them into the kernel's:
packed words go key-major, ``(B, N, KVH, W)`` — the order in which the
block-table gather of the paged pool already writes them — with N
padded to whole tiles (a flat ``(BH, N, W)`` caller is ``KVH = 1``);
±1 plane bytes go flat, ``(BH, N, L*P)``.  ``interpret=None`` (the
default) compiles with Mosaic on a TPU backend and interprets elsewhere
(:func:`repro.kernels.common.resolve_interpret`).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.common import resolve_interpret
from repro.kernels.socket_score.socket_score import (CHUNK, DEFAULT_BLOCK_N,
                                                     LANES,
                                                     socket_score_pallas)


@functools.partial(jax.jit, static_argnames=("num_tables", "num_planes",
                                             "tau", "block_n", "interpret"))
def _score_packed(bits, u, vnorm, length, *, num_tables, num_planes, tau,
                  block_n, interpret):
    b, kvh, n, _ = bits.shape
    # N padded to whole tiles (the tail's scores are sliced off): one
    # tile of whole 128-key slices, or tiles of whole inner steps
    n128 = -(-n // LANES) * LANES
    tile = n128 if n128 <= CHUNK else -(-block_n // CHUNK) * CHUNK
    n_pad = -(-n // tile) * tile
    # KV heads padded to a divisor of the 128 lanes (extra heads sliced)
    h_pad = 1 << (kvh - 1).bit_length()
    words = jnp.pad(jnp.transpose(bits, (0, 2, 1, 3)),
                    ((0, 0), (0, n_pad - n), (0, h_pad - kvh), (0, 0)))
    u = jnp.pad(u, ((0, 0), (0, h_pad - kvh)) + ((0, 0),) * 3)
    if vnorm is not None:
        vnorm = jnp.pad(vnorm, ((0, 0), (0, h_pad - kvh), (0, n_pad - n)))
    if length is not None:
        length = jnp.broadcast_to(jnp.asarray(length, jnp.int32), (b,))
    out = socket_score_pallas(words, u, vnorm, num_tables=num_tables,
                              num_planes=num_planes, tau=tau,
                              length=length, block_n=tile,
                              interpret=interpret)
    return out[:, :kvh, :n]


@functools.partial(jax.jit, static_argnames=("num_tables", "num_planes",
                                             "tau", "block_n", "interpret"))
def _score_int8(bits, u, vnorm, *, num_tables, num_planes, tau, block_n,
                interpret):
    return socket_score_pallas(bits, u, vnorm, num_tables=num_tables,
                               num_planes=num_planes, tau=tau,
                               block_n=block_n, interpret=interpret)


def socket_score(bits: jax.Array, u: jax.Array,
                 vnorm: Optional[jax.Array] = None, *, num_tables: int,
                 num_planes: int, tau: float,
                 length: Optional[jax.Array] = None,
                 block_n: int = DEFAULT_BLOCK_N,
                 interpret: Optional[bool] = None) -> jax.Array:
    """Score keys for one decode step.

    Shapes (model layout):
      bits   uint32 (B, KVH, N, W)  or (BH, N, W) packed words, or int8
             (..., N, L*P) ±1 plane bytes
      u      f32    (B, KVH, G, L, P) or (BH, G, L, P)
      vnorm         (B, KVH, N) or (BH, N) or None
      length int32  live keys per leading row ((B,) or (BH,)), a scalar
             for all, or None.  Packed words only: whole tiles past it
             score 0 (the selection masks those slots anyway).

    Returns scores f32 matching the leading layout: (B, KVH, N) / (BH, N).
    """
    interpret = resolve_interpret(interpret)
    flat = bits.ndim == 3
    if bits.dtype == jnp.int8:
        lead = bits.shape[:-2]
        n = bits.shape[-2]
        out = _score_int8(
            bits.reshape(-1, n, bits.shape[-1]),
            u.reshape(-1, *u.shape[-3:]),
            None if vnorm is None else vnorm.reshape(-1, n),
            num_tables=num_tables, num_planes=num_planes, tau=float(tau),
            block_n=block_n, interpret=interpret)
        return out.reshape(*lead, n)
    if flat:                                       # (BH, ...) as KVH = 1
        bits, u = bits[:, None], u[:, None]
        vnorm = None if vnorm is None else vnorm[:, None]
    out = _score_packed(bits, u, vnorm, length, num_tables=num_tables,
                        num_planes=num_planes, tau=float(tau),
                        block_n=block_n, interpret=interpret)
    return out[:, 0] if flat else out
