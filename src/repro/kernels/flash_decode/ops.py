"""Jitted wrapper for flash_decode, accepting the model's (B, KVH, ...)
layout and padding K to the block size with masked rows."""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.common import resolve_interpret
from repro.kernels.flash_decode.flash_decode import (DEFAULT_BLOCK_K,
                                                     flash_decode_pallas)


@functools.partial(jax.jit, static_argnames=("scale", "block_k", "interpret"))
def _decode_flat(q, k, v, mask, *, scale, block_k, interpret):
    return flash_decode_pallas(q, k, v, mask, scale=scale, block_k=block_k,
                               interpret=interpret)


def flash_decode(q: jax.Array, k: jax.Array, v: jax.Array,
                 mask: jax.Array, *, scale: float,
                 block_k: int = DEFAULT_BLOCK_K,
                 interpret: Optional[bool] = None) -> jax.Array:
    """Sparse decode attention.

    q (B, KVH, G, 1, hd) or (BH, G, hd); k/v (B, KVH, K, hd) or (BH, K, hd);
    mask (B, KVH, K) / (BH, K).  Returns attention output in q's layout.
    """
    interpret = resolve_interpret(interpret)
    orig5 = q.ndim == 5
    if orig5:
        b, kvh, g, t, hd = q.shape
        assert t == 1
        q2 = q.reshape(b * kvh, g, hd)
        k2 = k.reshape(b * kvh, *k.shape[2:])
        v2 = v.reshape(b * kvh, *v.shape[2:])
        m2 = mask.reshape(b * kvh, mask.shape[-1])
    else:
        q2, k2, v2, m2 = q, k, v, mask
    kk = k2.shape[1]
    blk = min(block_k, kk)
    pad = (-kk) % blk
    if pad:
        k2 = jnp.pad(k2, ((0, 0), (0, pad), (0, 0)))
        v2 = jnp.pad(v2, ((0, 0), (0, pad), (0, 0)))
        m2 = jnp.pad(m2, ((0, 0), (0, pad)))
    out = _decode_flat(q2, k2, v2, m2, scale=float(scale), block_k=blk,
                       interpret=interpret)
    if orig5:
        out = out.reshape(b, kvh, g, 1, hd).astype(q.dtype)
    return out
