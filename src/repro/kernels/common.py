"""Pieces shared by the Pallas kernels: where they run, and the
Mosaic-lowerable building blocks their bodies are made of.

Layout rules the helpers keep (Mosaic tiles the last two dimensions of
every block and value by (8, 128) for 32-bit types): a per-token vector
is a ``(1, n)`` lane row, a per-query-head statistic a ``(G, 1)``
column, and nothing is reshaped across the lane axis.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30
HIGHEST = jax.lax.Precision.HIGHEST
NT = (((1,), (1,)), ((), ()))             # dot_general dims of a @ b.T
NN = (((1,), (0,)), ((), ()))             # dot_general dims of a @ b


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """The one place that picks Pallas interpret mode: kernels compile
    with Mosaic on a TPU backend and run in the interpreter (same
    semantics, interpreter speed) everywhere else.  An explicit bool
    wins."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)


def compiles_with_mosaic() -> bool:
    """Whether kernels compile here (a TPU backend): the one predicate a
    served path reads to route through a kernel without a flag."""
    return not resolve_interpret(None)


def head_row(ref, h) -> jax.Array:
    """Row ``h`` of a ``(1, KVH, n)`` block as an f32 ``(1, n)`` lane row.

    Per-head ``(NB, KVH, n)`` leaves are fetched as whole ``(1, KVH, n)``
    tiles: a ``(1, 1, n)`` block would put a 1 against KVH on the
    sublane axis, which Mosaic refuses.  The masked sum is exact."""
    tile = ref[0].astype(jnp.float32)                     # (KVH, n)
    rows = jax.lax.broadcasted_iota(jnp.int32, tile.shape, 0)
    return jnp.sum(jnp.where(rows == h, tile, 0.0), axis=0, keepdims=True)


def unpack_signs(words: jax.Array) -> jax.Array:
    """uint32 (n, W) packed words -> f32 ±1 (n, W*32); flat bit
    ``w*32 + b`` is bit ``b`` of word ``w`` (``hashing.pack_signs``)."""
    n, w = words.shape
    shifts = jax.lax.broadcasted_iota(jnp.uint32, (1, 1, 32), 2)
    bits = (words[:, :, None] >> shifts) & jnp.uint32(1)
    bits = bits.reshape(n, w * 32).astype(jnp.int32)
    return bits.astype(jnp.float32) * 2.0 - 1.0


def table_scores(signs: jax.Array, u: jax.Array, logz: Optional[jax.Array],
                 *, num_planes: int, tau: float) -> jax.Array:
    """Group-summed per-key hash scores as an f32 ``(1, n)`` row.

    ``signs`` f32 ±1 (n, l_pad*P) — table-major, plane-minor; ``u`` f32
    (GS, l_pad*P) in the same flat order; ``logz`` f32 (GS, l_pad).

    With ``logz`` this is SOCKET's factorized soft-collision score
    ``sum_g sum_l exp(<S_l, u_gl>/tau - logZ_gl)`` (padding tables carry
    ``logZ = +1e30`` and contribute 0).  With ``logz=None`` it is the
    hard-LSH collision count ``sum_g sum_l 1[<S_l, u_gl> >= P]`` for ±1
    query plane signs ``u`` (0 on padding tables, which never reach P).

    The per-table plane sum is a matmul against the 0/1 segment matrix
    ``seg[k, l] = (k // P == l)`` — no (n, L, P) lane reshape — and
    ±1 * u is exact, so it is the plane dot.  Tables are summed before
    the group, as in the XLA reference."""
    n, nbits = signs.shape
    l_pad = nbits // num_planes
    kk = jax.lax.broadcasted_iota(jnp.int32, (nbits, l_pad), 0)
    ll = jax.lax.broadcasted_iota(jnp.int32, (nbits, l_pad), 1)
    seg = (kk // num_planes == ll).astype(jnp.float32)
    ones = jnp.ones((1, l_pad), jnp.float32)
    scores = jnp.zeros((1, n), jnp.float32)
    for g in range(u.shape[0]):                   # static score-group loop
        dots = jax.lax.dot_general(signs * u[g:g + 1], seg, NN,
                                   precision=HIGHEST)        # (n, l_pad)
        if logz is not None:
            z = jnp.exp(dots / tau - logz[g:g + 1])
        else:
            z = (dots >= jnp.float32(num_planes)).astype(jnp.float32)
        scores = scores + jax.lax.dot_general(ones, z, NT,
                                              precision=HIGHEST)
    return scores


def init_softmax(m_scr, l_scr, acc_scr) -> None:
    """Reset the online-softmax scratch (``m``/``l`` (G, 1), ``acc``
    (G, hd), all f32)."""
    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)


def fold_page(q, k, v, keep, m_scr, l_scr, acc_scr, *, scale: float,
              k_scale=None, v_scale=None, softcap: float = 0.0) -> None:
    """Fold one block of K/V rows into the running online softmax.

    ``q`` f32 (G, hd); ``k``/``v`` (n, hd) in storage dtype; ``keep``
    bool (1, n); ``k_scale``/``v_scale`` f32 (1, n) per-row dequant
    scales of quantized pages, applied to the logits and to the softmax
    weights (the same products as dequantizing the rows, never in HBM);
    ``softcap`` > 0 applies ``c * tanh(s / c)`` before masking."""
    k = k.astype(jnp.float32)
    v = v.astype(jnp.float32)
    s = jax.lax.dot_general(q, k, NT, precision=HIGHEST)     # (G, n)
    if k_scale is not None:
        s = s * k_scale
    s = s * scale
    if softcap:                                   # static no-op at 0.0
        s = softcap * jnp.tanh(s / softcap)
    s = jnp.where(keep, s, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.where(keep, jnp.exp(s - m_new), 0.0)
    l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    if v_scale is not None:
        p = p * v_scale
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        p, v, NN, precision=HIGHEST)
    m_scr[...] = m_new


def finish_softmax(l_scr, acc_scr) -> jax.Array:
    """``acc / l`` — f32 (G, hd); an all-masked row yields 0, not NaN."""
    return acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
