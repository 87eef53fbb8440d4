"""Pallas TPU kernel: SOCKET soft-collision scoring (paper Algorithm 4).

TPU adaptation of the paper's CUDA scoring kernel (DESIGN.md §2): instead
of gathering per-key bucket probabilities from a LUT (random-access —
wrong primitive for TPU), the kernel streams the *bit-packed* sign matrix
from HBM and evaluates the exact factorized score

    score[n] = vnorm[n] * sum_g sum_l exp( <S_nl, u_gl> / tau - logZ_gl )

in one pass: each key's packed words are read once and one float32 score
per (request, KV head, key) is written.  Nothing per plane or per table
reaches HBM.

Packed words (``bits_storage="packed"``) arrive in the key-major order
``(B, N, KVH, W)`` in which the block-table gather of the paged pool
writes them, viewed as ``(B, N*KVH, W)`` rows of (key, head).  Each
128-row slice is transposed in VMEM (XLU) to ``(W, 128)``, so word ``w``
of 8 slices is one dense ``(8, 128)`` vreg of 1,024 (key, head) pairs;
lane ``j`` holds head ``j % KVH``.  With ``x = u / tau`` each plane's
log probability is

    log p_gli(S) = -softplus(-2 S x_gli),   S = ±1,

and the table's ``<S_l, u_gl>/tau - logZ_gl`` is their sum over the
planes.  So per table and plane the body tests one bit (a static word
and shift) and adds one of two lane rows, ``log p(+1)`` or
``log p(-1)`` of each lane's head (loaded once per step of ``CHUNK``
keys, replicated over the sublanes), into ``G`` float32 accumulators on
the VPU; at the
end of the table ``exp(acc_g)`` joins the score.  Every term is <= 0,
so no large logit cancels against ``logZ``.  No MXU, no segment matrix,
no unpacked signs; the alignment bits past ``L*P`` are never read.
Tables are visited in groups of ``lcm(P, 32) / P`` (the tables of
``lcm(P, 32) / 32`` whole words, 16 tables in 5 words at P=10): a
``fori_loop`` over groups with a static body, plus a static tail.  The
scores leave as ``(B, N*KVH)`` (key, head) rows, one f32 per pair.

Tiles that lie wholly past a request's length (``length``, scalar
prefetch) write zeros and skip the loop; their words are not fetched
(the index map pins them to the last live tile).  ``value_aware_topk``
masks those slots to -inf anyway.

±1 plane bytes (``bits_storage="int8"``, ``(BH, N, L*P)``): the plane
dot is ``(signs * u) @ seg`` against the 0/1 segment matrix
(``kernels.common.table_scores``), one ``(block_n, L*P)`` tile per step.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import table_scores

DEFAULT_BLOCK_N = 1024          # keys per packed-words grid step
CHUNK = 1024                    # keys per inner step
INT8_BLOCK_N = 512              # keys per ±1-byte grid step
LANES = 128


def _table_group(p: int):
    """(tables, words) of one group: the tables of whole 32-bit words."""
    return math.lcm(p, 32) // p, math.lcm(p, 32) // 32


def _packed_kernel(len_ref, words_ref, logp_ref, *refs, num_tables: int,
                   num_planes: int, groups: int, block_n: int, chunk: int,
                   kvh: int):
    """One (b, n-block) tile: ``words_ref`` (1, block_n*KVH, W) uint32
    (key, head) rows; ``logp_ref`` (1, K, 128) f32: row ``k`` holds, on
    lane ``j``, coefficient ``k`` of head ``j % KVH`` — the plane log
    probabilities of S = +1 and -1 in (group, table, plane, g, sign)
    order; ``refs`` are ``vnorm_ref`` (weighted calls only) and
    ``out_ref``, (1, block_n*KVH/128, 128) f32, then the VMEM scratch
    ``words`` (W*slices, 128): word ``w`` of slice ``s`` at row
    ``w*slices + s``."""
    vnorm_ref, out_ref, words = refs if len(refs) == 3 else (None, *refs)
    b, tile = pl.program_id(0), pl.program_id(1)
    live = tile * block_n < len_ref[b]
    num_words = words_ref.shape[2]
    p = num_planes
    per_group, words_per_group = _table_group(p)
    full_groups, tail = divmod(num_tables, per_group)
    slices = chunk * kvh // LANES               # 128-row slices per step
    per_group_rows = per_group * p * groups * 2

    def tables(score, j, count):
        """Fold ``count`` tables of group ``j`` into ``score``."""
        loaded = {}
        for t in range(count):
            acc = [None] * groups
            for i in range(p):
                w, s = divmod(t * p + i, 32)
                if w not in loaded:
                    row = pl.multiple_of((j * words_per_group + w) * slices,
                                         slices)
                    loaded[w] = words[pl.ds(row, slices), :]
                hit = (loaded[w] & jnp.uint32(1 << s)) != 0
                for g in range(groups):
                    k = j * per_group_rows + ((t * p + i) * groups + g) * 2
                    term = jnp.where(hit, logp_ref[0, pl.ds(k, 1), :],
                                     logp_ref[0, pl.ds(k + 1, 1), :])
                    acc[g] = term if acc[g] is None else acc[g] + term
            for g in range(groups):
                score = score + jnp.exp(acc[g])
        return score

    @pl.when(live)
    def _():
        def step(c, carry):
            # each 128-row slice of (key, head) rows turns word-major and
            # lands on its own sublane of every word
            for sl in range(slices):
                start = pl.multiple_of((c * slices + sl) * LANES, LANES)
                words[pl.ds(sl, num_words, stride=slices), :] = \
                    words_ref[0, pl.ds(start, LANES), :].T
            score = jnp.zeros((slices, LANES), jnp.float32)
            score = jax.lax.fori_loop(
                0, full_groups, lambda j, sc: tables(sc, j, per_group),
                score)
            if tail:
                score = tables(score, full_groups, tail)
            rows = pl.ds(pl.multiple_of(c * slices, slices), slices)
            if vnorm_ref is not None:
                score = score * vnorm_ref[0, rows, :]
            out_ref[0, rows, :] = score
            return carry

        jax.lax.fori_loop(0, block_n // chunk, step, 0)

    @pl.when(jnp.logical_not(live))
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)


def _int8_kernel(bits_ref, u_ref, logz_ref, vnorm_ref, out_ref, *,
                 num_planes: int, tau: float):
    """One (bh, n-block) tile of ±1 plane bytes."""
    signs = bits_ref[0].astype(jnp.int32).astype(jnp.float32)
    scores = table_scores(signs, u_ref[0], logz_ref[0],
                          num_planes=num_planes, tau=tau)   # (1, block_n)
    out_ref[0] = scores * vnorm_ref[0]


def _packed_call(words, u, vnorm, length, *, num_planes, tau, block_n,
                 interpret):
    b, n, kvh, w = words.shape
    g, l, p = u.shape[2:]
    block_n = min(block_n, n)
    chunk = min(CHUNK, block_n)
    if (LANES % kvh or n % block_n or block_n % chunk
            or (chunk * kvh) % LANES):
        raise ValueError(f"N={n}, KVH={kvh} do not tile by block_n="
                         f"{block_n} (128 (key, head) rows a slice)")
    rows = block_n * kvh // LANES
    # log p(S = ±1) = -softplus(∓2u/tau) in (group, table, plane, g,
    # sign) order, one 128-lane row each: lane j is head j % KVH
    per_group, _ = _table_group(p)
    n_groups = -(-l // per_group)
    x = 2.0 * u / tau                                      # (B,KVH,G,L,P)
    logp = -jnp.logaddexp(0.0, jnp.stack([-x, x], axis=-1))
    logp = jnp.pad(logp, ((0, 0), (0, 0), (0, 0),
                          (0, n_groups * per_group - l), (0, 0), (0, 0)))
    logp = logp.reshape(b, kvh, g, n_groups, per_group, p, 2)
    logp = jnp.transpose(logp, (0, 3, 4, 5, 2, 6, 1)).reshape(b, -1, kvh)
    logp = jnp.tile(logp, (1, 1, LANES // kvh))
    if length is None:
        length = jnp.full((b,), n, jnp.int32)

    def live_tile(i, lens, r):
        # tiles past the length keep the last live tile's block, so the
        # pipeline fetches nothing for them
        last = jnp.maximum((lens[r] + block_n - 1) // block_n - 1, 0)
        return jnp.minimum(i, last)

    in_specs = [
        pl.BlockSpec((1, block_n * kvh, w),
                     lambda r, i, lens: (r, live_tile(i, lens, r), 0)),
        pl.BlockSpec((1,) + logp.shape[1:], lambda r, i, lens: (r, 0, 0)),
    ]
    args = [words.reshape(b, n * kvh, w), logp]
    if vnorm is not None:
        in_specs.append(pl.BlockSpec(
            (1, rows, LANES),
            lambda r, i, lens: (r, live_tile(i, lens, r), 0)))
        args.append(jnp.transpose(vnorm, (0, 2, 1)).reshape(
            b, n * kvh // LANES, LANES))
    kernel = functools.partial(
        _packed_kernel, num_tables=l, num_planes=p, groups=g,
        block_n=block_n, chunk=chunk, kvh=kvh)
    # VMEM: double-buffered blocks (the words lane-padded) + the scratch;
    # many group members make the coefficient block outgrow the 16 MiB
    # default
    f32_rows = block_n * kvh + logp.shape[1] + 2 * rows
    vmem = 2 * 4 * LANES * f32_rows + 4 * LANES * w * chunk * kvh // LANES
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, n // block_n),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, rows, LANES),
                                   lambda r, i, lens: (r, i, 0)),
            scratch_shapes=[pltpu.VMEM((w * chunk * kvh // LANES, LANES),
                                       jnp.uint32)],
        ),
        out_shape=jax.ShapeDtypeStruct((b, n * kvh // LANES, LANES),
                                       jnp.float32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=max(16 << 20, vmem + (4 << 20))),
        interpret=interpret,
    )(jnp.asarray(length, jnp.int32), *args)
    return jnp.transpose(out.reshape(b, n, kvh), (0, 2, 1))


def _int8_call(bits, u, logz, vnorm, *, num_planes, tau, block_n,
               interpret):
    bh, n, _ = bits.shape
    g, l, p = u.shape[1:]
    block_n = min(block_n, INT8_BLOCK_N, n)
    while n % block_n:
        block_n //= 2
    kernel = functools.partial(_int8_kernel, num_planes=num_planes,
                               tau=float(tau))
    # (BH, 1, N) rows: a (1, block_n) block of a (BH, N) array would put
    # a 1 against BH on the sublane axis, which Mosaic refuses
    out = pl.pallas_call(
        kernel,
        grid=(bh, n // block_n),
        in_specs=[
            pl.BlockSpec((1, block_n, l * p), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, g, l * p), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, g, l), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, 1, block_n), lambda b, i: (b, 0, i)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_n), lambda b, i: (b, 0, i)),
        out_shape=jax.ShapeDtypeStruct((bh, 1, n), jnp.float32),
        interpret=interpret,
    )(bits, u.reshape(bh, g, l * p), logz, vnorm.reshape(bh, 1, n))
    return out.reshape(bh, n)


def socket_score_pallas(bits: jax.Array, u: jax.Array,
                        vnorm: Optional[jax.Array], *, num_tables: int,
                        num_planes: int, tau: float,
                        length: Optional[jax.Array] = None,
                        block_n: int = DEFAULT_BLOCK_N,
                        interpret: bool) -> jax.Array:
    """Launch the scoring kernel.

    Args:
      bits:  uint32 (B, N, KVH, W) packed sign words, key-major (N a
             multiple of the tile, or one tile; KVH dividing 128, and
             N*KVH a multiple of 128), or int8 (BH, N, L*P) ±1 plane
             bytes (``bits_storage="int8"`` — format inferred from the
             dtype).
      u:     f32 (B, KVH, G, L, P) query soft-hash; (BH, G, L, P) with
             int8 bytes.
      vnorm: f32 value norms, (B, KVH, N) / (BH, N), or None.
      length: int32 (B,) live keys per request, or None (all N).  Packed
             words only: tiles wholly past it score 0.

    Returns:
      f32 (B, KVH, N) / (BH, N) scores (group-summed, value-weighted).
    """
    l, p = u.shape[-2:]
    if l != num_tables or p != num_planes:
        raise ValueError("u shape mismatch")
    u = u.astype(jnp.float32)
    if vnorm is not None:
        vnorm = vnorm.astype(jnp.float32)
    if bits.dtype == jnp.int8:
        if bits.shape[2] != l * p:
            raise ValueError(
                f"int8 bits width {bits.shape[2]} != L*P = {l * p}")
        if vnorm is None:
            vnorm = jnp.ones(bits.shape[:2], jnp.float32)
        from repro.core import socket as sk
        logz = sk.log_normalizer(u, tau)                        # (BH,G,L)
        return _int8_call(bits, u, logz, vnorm, num_planes=p, tau=tau,
                          block_n=block_n, interpret=interpret)
    if bits.shape[3] * 32 < l * p:
        raise ValueError(f"{bits.shape[3]} words hold fewer than L*P "
                         f"= {l * p} bits")
    return _packed_call(bits, u, vnorm, length, num_planes=p,
                        tau=float(tau), block_n=block_n,
                        interpret=interpret)
