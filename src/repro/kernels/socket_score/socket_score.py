"""Pallas TPU kernel: SOCKET soft-collision scoring (paper Algorithm 4).

TPU adaptation of the paper's CUDA scoring kernel (DESIGN.md §2): instead
of gathering per-key bucket probabilities from a LUT (random-access —
wrong primitive for TPU), the kernel streams the *bit-packed* sign matrix
from HBM, unpacks it in-register with shift/mask ops, and evaluates the
exact factorized score

    score[n] = vnorm[n] * sum_g sum_l exp( <S_nl, u_gl> / tau - logZ_gl )

Memory behaviour (the point of SOCKET): per token the kernel reads
``W*4 = 80`` bytes of packed bits + 4 bytes of vnorm instead of the 256 B
of bf16 keys a dense decode reads — a 3.2x HBM-traffic reduction, which is
what makes sparse decode profitable at long context on TPU v5e
(819 GB/s HBM).

Tiling: grid = (BH, N // block_n).  Per step the kernel holds
  bits  (block_n, W)     uint32   — block_n=512, W=20 → 40 KiB
  u     (G, L_pad * P)   f32      (VMEM resident)
  logz  (G, L_pad)       f32
  vnorm (1, block_n)     f32
  out   (1, block_n)     f32
comfortably inside VMEM.  vnorm and the output travel as ``(BH, 1, N)``
rows so every block is tile-legal for Mosaic.

Tables are processed in a padded ``L_pad = W*32/P`` view with the
padding neutralised via logZ = +1e30 (=> exp(-1e30) = 0 contribution).
The per-table plane dot is ``(signs * u) @ seg`` with the 0/1 segment
matrix ``seg[k, l] = (k // P == l)`` (``kernels.common.table_scores``),
so the unpacked ``(block_n, W*32)`` signs are never reshaped across
lanes.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import table_scores, unpack_signs

DEFAULT_BLOCK_N = 512


def _score_kernel(bits_ref, u_ref, logz_ref, vnorm_ref, out_ref, *,
                  num_planes: int, tau: float, bits_format: str = "packed"):
    """One (bh, n-block) tile."""
    if bits_format == "packed":
        signs = unpack_signs(bits_ref[0])        # (block_n, W*32) ±1
    else:                                        # "int8": ±1 plane bytes
        signs = bits_ref[0].astype(jnp.int32).astype(jnp.float32)
    # (1, block_n); padding tables contribute 0 via logz = +1e30
    scores = table_scores(signs, u_ref[0], logz_ref[0],
                          num_planes=num_planes, tau=tau)
    out_ref[0] = scores * vnorm_ref[0]


def socket_score_pallas(bits: jax.Array, u: jax.Array,
                        vnorm: Optional[jax.Array], *, num_tables: int,
                        num_planes: int, tau: float,
                        block_n: int = DEFAULT_BLOCK_N,
                        interpret: bool) -> jax.Array:
    """Launch the scoring kernel.

    Args:
      bits:  uint32 (BH, N, W) packed sign bits, or int8 (BH, N, L*P)
             ±1 plane bytes (``bits_storage="int8"`` — format inferred
             from the dtype; no unpack, no table padding).
      u:     f32 (BH, G, L, P) query soft-hash.
      vnorm: f32 (BH, N) value norms, or None.

    Returns:
      f32 (BH, N) scores (group-summed, value-weighted).
    """
    bh, n, w = bits.shape
    _, g, l, p = u.shape
    if l != num_tables or p != num_planes:
        raise ValueError("u shape mismatch")
    bits_format = "int8" if bits.dtype == jnp.int8 else "packed"
    if bits_format == "packed":
        if (w * 32) % num_planes:
            raise ValueError(
                f"packed width {w*32} bits not a multiple of P="
                f"{num_planes}; choose P dividing 32*W")
        l_pad = (w * 32) // num_planes
    else:
        if w != l * p:
            raise ValueError(
                f"int8 bits width {w} != L*P = {l * p}")
        l_pad = l                                 # no padding tables

    # logZ (+inf on padding tables kills their contribution exactly)
    from repro.core import socket as sk
    logz = sk.log_normalizer(u.astype(jnp.float32), tau)       # (BH,G,L)
    pad_l = l_pad - l
    u_pad = jnp.pad(u.astype(jnp.float32),
                    ((0, 0), (0, 0), (0, pad_l), (0, 0)))
    logz_pad = jnp.pad(logz, ((0, 0), (0, 0), (0, pad_l)),
                       constant_values=jnp.float32(1e30))

    if vnorm is None:
        vnorm = jnp.ones((bh, n), jnp.float32)
    vnorm = vnorm.astype(jnp.float32)

    if n % block_n:
        raise ValueError(f"N={n} not a multiple of block_n={block_n}")

    # (BH, 1, N) rows: a (1, block_n) block of a (BH, N) array would put
    # a 1 against BH on the sublane axis, which Mosaic refuses
    kernel = functools.partial(_score_kernel, num_planes=num_planes,
                               tau=float(tau), bits_format=bits_format)
    nbits = l_pad * num_planes
    out = pl.pallas_call(
        kernel,
        grid=(bh, n // block_n),
        in_specs=[
            pl.BlockSpec((1, block_n, w), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, g, nbits), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, g, l_pad), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, 1, block_n), lambda b, i: (b, 0, i)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_n), lambda b, i: (b, 0, i)),
        out_shape=jax.ShapeDtypeStruct((bh, 1, n), jnp.float32),
        interpret=interpret,
    )(bits, u_pad.reshape(bh, g, nbits), logz_pad, vnorm.reshape(bh, 1, n))
    return out.reshape(bh, n)
