"""Kernel differential tests, driven by ``kernel_harness``.

Every Pallas op (socket_score, flash_decode, flash_prefill, and the
fused paged_attention kernel) is pinned to its ``ref.py`` oracle through
one parametrized differential test; the bitwise-or-tolerance policy is
declared once per op in the registry below, not per test.  Property
tests (Hypothesis + fixed-seed) pin the fused kernel's *selected set*
exactly to the reference ``value_aware_topk`` semantics.

All tests run the kernels in interpret mode on CPU (identical code
paths lower to TPU) and carry the ``kernels`` marker so CI can split
them from the fast tier-1 job.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_compat import given, settings, st
from kernel_harness import (BITWISE, KernelCase, KernelOp, ParityPolicy,
                            all_cases, run_differential)

from repro.core import hashing, socket
from repro.kernels.flash_decode import flash_decode, flash_decode_ref
from repro.kernels.flash_prefill import flash_prefill, flash_prefill_ref
from repro.kernels.paged_attention import (paged_hard_lsh_attend,
                                           paged_hard_lsh_attend_ref,
                                           paged_quest_attend,
                                           paged_quest_attend_ref,
                                           paged_ring_attend,
                                           paged_ring_attend_ref,
                                           paged_socket_attend,
                                           paged_socket_attend_ref)
from repro.kernels.socket_score import socket_score, socket_score_ref

pytestmark = pytest.mark.kernels


# --------------------------------------------------------------- builders

def _build_socket_score(case):
    p, l, n, g, bh, d, block_n, weighted = (
        case.kwargs[k] for k in
        ("p", "l", "n", "g", "bh", "d", "block_n", "weighted"))
    bits_fmt = case.kwargs.get("bits_fmt", "packed")
    lengths = case.kwargs.get("lengths")
    rng = jax.random.PRNGKey(p * l + n + block_n)
    kk, kq, kw, kv = jax.random.split(rng, 4)
    w = hashing.make_hash_params(kw, d, p, l)
    keys = jax.random.normal(kk, (bh, n, d))
    q = jax.random.normal(kq, (bh, g, d))
    signs = hashing.hash_keys_signs(w, keys)
    if bits_fmt == "int8":
        # bits_storage="int8": ±1 plane bytes (BH, N, L*P) — the kernel
        # skips the unpack and the padding tables entirely
        bits = (signs.astype(jnp.int8) * 2 - 1).reshape(bh, n, l * p)
    else:
        bits = hashing.pack_signs(signs)
    # u_scale > 1 pushes u/tau across the exp range (u itself is tanh-
    # bounded by 1/sqrt(d))
    u = socket.soft_hash_query(w, q) * case.kwargs.get("u_scale", 1.0)
    vnorm = (jax.random.uniform(kv, (bh, n)) + 0.5) if weighted else None
    length = None if lengths is None else jnp.asarray(lengths, jnp.int32)
    words = bits
    if case.kwargs.get("lane_pad"):
        # rows padded to whole 128-lane rows, as the bits leaf stores
        # them: the padding (all ones here) is never read
        words = jnp.pad(bits, ((0, 0), (0, 0), (0, 128 - bits.shape[-1])),
                        constant_values=np.uint32(0xFFFFFFFF))
    out = socket_score(words, u, vnorm, num_tables=l, num_planes=p,
                       tau=0.4, length=length, block_n=block_n)
    ref = socket_score_ref(bits, u, vnorm, num_tables=l, num_planes=p,
                           tau=0.4)
    # the XLA scorer the served path runs where the kernel does not
    scfg = socket.SocketConfig(num_planes=p, num_tables=l, tau=0.4,
                               sparsity=4.0, sink_tokens=4, window_tokens=4,
                               min_k=4, bits_storage=bits_fmt)
    vn = jnp.ones((bh, n)) if vnorm is None else vnorm
    with jax.default_matmul_precision("float32"):
        xla = jnp.sum(socket.soft_scores_factorized(
            scfg, bits[:, None], u), axis=1) * vn
    live = np.ones((bh, n), bool) if lengths is None else (
        np.arange(n)[None] < np.asarray(lengths)[:, None])
    out_np = np.asarray(out)
    cmps = [("scores", out_np[live], np.asarray(ref)[live]),
            ("xla-f32", out_np[live], np.asarray(xla)[live])]
    if lengths is not None:
        # whole tiles (the case's block_n keys) past a row's length
        # score 0 (never selected)
        dead = np.arange(n)[None] >= -(-np.asarray(lengths)[:, None]
                                       // block_n) * block_n
        cmps.append(("dead-tiles", out_np[dead], np.zeros(dead.sum()),
                     BITWISE))
    # value-aware top-k picks the same keys (inputs have no exact ties)
    k = socket.topk_budget(scfg, n)
    n_live = n if length is None else length
    picks = [socket.value_aware_topk(scfg, s, vn, k=k, length=n_live,
                                     n_total=n)[0] for s in (out, ref)]
    cmps.append(("topk", picks[0], picks[1], BITWISE))
    return cmps


def _build_flash_decode(case):
    bh, g, k, hd, dtype, block_k = (
        case.kwargs[x] for x in ("bh", "g", "k", "hd", "dtype", "block_k"))
    rng = jax.random.PRNGKey(k + hd + block_k)
    k1, k2, k3, k4 = jax.random.split(rng, 4)
    q = jax.random.normal(k1, (bh, g, hd), dtype)
    kk = jax.random.normal(k2, (bh, k, hd), dtype)
    vv = jax.random.normal(k3, (bh, k, hd), dtype)
    mask = jax.random.bernoulli(k4, 0.7, (bh, k)).at[:, 0].set(True)
    out = flash_decode(q, kk, vv, mask, scale=1 / np.sqrt(hd),
                       block_k=block_k)
    ref = flash_decode_ref(q, kk, vv, mask, scale=1 / np.sqrt(hd))
    return [("attn", out, ref)]


def _build_flash_prefill(case):
    bh, s, hd, window, dtype = (
        case.kwargs[x] for x in ("bh", "s", "hd", "window", "dtype"))
    rng = jax.random.PRNGKey(s + hd + window)
    k1, k2, k3 = jax.random.split(rng, 3)
    q = jax.random.normal(k1, (bh, s, hd), dtype)
    k = jax.random.normal(k2, (bh, s, hd), dtype)
    v = jax.random.normal(k3, (bh, s, hd), dtype)
    out = flash_prefill(q, k, v, scale=1 / np.sqrt(hd), window=window,
                        block_q=128, block_k=128)
    ref = flash_prefill_ref(q, k, v, scale=1 / np.sqrt(hd), window=window)
    return [("attn", out, ref)]


def _paged_fixture(seed, b, kvh, g, gs, nb, bs, hd, p, l, sink, window,
                   lengths, dtype=jnp.float32, dup=False, tau=0.4,
                   kv_dtype=None):
    """Paged-pool inputs with shuffled physical blocks (block 0 = trash).

    ``kv_dtype`` "int8"/"fp8" stores the K/V pages quantized with
    per-row absmax scale pools riding along (passed to kernel and
    oracle as ``k_scale``/``v_scale`` — both dequantize the same
    values, so selection stays bitwise)."""
    from repro.models.backends import kvquant

    rng = np.random.default_rng(seed)
    n, d = nb * bs, 32
    w = hashing.make_hash_params(jax.random.PRNGKey(seed), d, p, l)
    keys = rng.normal(size=(b, kvh, n, d)).astype(np.float32)
    if dup:
        # exact duplicate key content -> exact score ties at selection
        keys[:, :, 1::2] = keys[:, :, 0::2]
    vals = rng.normal(size=(b, kvh, n, d)).astype(np.float32)
    bits = hashing.pack_signs(hashing.hash_keys_signs(w, jnp.asarray(keys)))
    vnorm = jnp.linalg.norm(jnp.asarray(vals), axis=-1).astype(jnp.bfloat16)
    kc = jnp.asarray(rng.normal(size=(b, kvh, n, hd)), dtype)
    vc = jnp.asarray(rng.normal(size=(b, kvh, n, hd)), dtype)
    q = jnp.asarray(rng.normal(size=(b, kvh, g, hd)), jnp.float32)
    u = socket.soft_hash_query(
        w, jnp.asarray(rng.normal(size=(b, kvh, gs, d)), jnp.float32))

    bt = 1 + rng.permutation(b * nb).reshape(b, nb).astype(np.int32)

    def pageify(leaf):
        arr = np.asarray(leaf)
        pool = np.zeros((1 + b * nb, kvh, bs) + arr.shape[3:], arr.dtype)
        for i in range(b):
            for j in range(nb):
                pool[bt[i, j]] = arr[i, :, j * bs:(j + 1) * bs]
        return jnp.asarray(pool)

    scfg = socket.SocketConfig(num_planes=p, num_tables=l, tau=tau,
                               sink_tokens=sink, window_tokens=window,
                               min_k=4, sparsity=4.0)
    kq = socket.topk_budget(scfg, n)
    length = jnp.asarray(lengths, jnp.int32)
    budget = socket.dynamic_topk_budget(scfg, length, kq)
    kw = dict(length=length, budget=budget, num_tables=l, num_planes=p,
              tau=tau, scale=1 / np.sqrt(hd), sink_tokens=sink,
              window_tokens=window)
    if kv_dtype is not None:
        kc, ks = kvquant.quantize(kc, kv_dtype)
        vc, vs = kvquant.quantize(vc, kv_dtype)
        kw.update(k_scale=pageify(ks), v_scale=pageify(vs))
    return (q, pageify(kc), pageify(vc), pageify(bits), pageify(vnorm), u,
            jnp.asarray(bt)), kw, kq


def _build_paged_attention(case):
    args, kw, kq = _paged_fixture(**case.kwargs)
    out, sel = paged_socket_attend(*args, with_selection=True, **kw)
    ref, sel_ref = paged_socket_attend_ref(*args, top_k=kq, **kw)
    return [("attn", out, ref), ("selection", sel, sel_ref, BITWISE)]


def _build_paged_hard_lsh(case):
    """Hard-collision variant: same pool fixture, the query-side soft
    hash replaced by its ±1 plane signs (``tau`` drops out)."""
    args, kw, kq = _paged_fixture(**case.kwargs)
    q, kp, vp, bits, vn, u, bt = args
    u_signs = jnp.where(u >= 0, 1.0, -1.0).astype(jnp.float32)
    kw = {k: v for k, v in kw.items() if k != "tau"}
    out, sel = paged_hard_lsh_attend(q, kp, vp, bits, vn, u_signs, bt,
                                     with_selection=True, **kw)
    ref, sel_ref = paged_hard_lsh_attend_ref(q, kp, vp, bits, vn, u_signs,
                                             bt, top_k=kq, **kw)
    return [("attn", out, ref), ("selection", sel, sel_ref, BITWISE)]


def _quest_fixture(seed, b, kvh, g, nb, bs, hd, ps, sink, window, lengths,
                   sparsity=4.0, min_pages=2, dtype=jnp.float32, dup=False,
                   kv_dtype=None):
    """Paged K/V pool plus per-page kmin/kmax stat pools (ppb = bs / ps
    stat rows per physical block), shuffled block table, ragged lengths.

    ``kv_dtype`` "int8"/"fp8" quantizes the K/V pages (per-row scales
    ride along) and — matching ``quest.stats_from_quantized`` — computes
    the kmin/kmax stats from the quantized *round trip*, so the page
    bounds stay sound for the keys the attend phase dequantizes."""
    from repro.baselines import quest as quest_mod
    from repro.models.backends import kvquant

    rng = np.random.default_rng(seed)
    n = nb * bs
    kc = rng.normal(size=(b, kvh, n, hd)).astype(np.float32)
    if dup:
        # identical page content -> exact page-score ties at selection
        pages = kc.reshape(b, kvh, n // ps, ps, hd)
        pages[:, :, 1::2] = pages[:, :, 0::2]
        kc = pages.reshape(b, kvh, n, hd)
    vc = rng.normal(size=(b, kvh, n, hd)).astype(np.float32)
    q = jnp.asarray(rng.normal(size=(b, kvh, g, hd)), jnp.float32)
    if kv_dtype is not None:
        kq_pages, ks = kvquant.quantize(jnp.asarray(kc), kv_dtype)
        vq_pages, vs = kvquant.quantize(jnp.asarray(vc), kv_dtype)
        stats_src = np.asarray(kvquant.dequantize(kq_pages, ks))
        k_store, v_store = kq_pages, vq_pages
    else:
        stats_src = kc
        k_store = jnp.asarray(kc, dtype)
        v_store = jnp.asarray(vc, dtype)
    # page stats stay f32 even for bf16/quantized K/V (selection is
    # compared bitwise; only the attention math runs in the case dtype)
    kmin = stats_src.reshape(b, kvh, n // ps, ps, hd).min(axis=3)
    kmax = stats_src.reshape(b, kvh, n // ps, ps, hd).max(axis=3)

    bt = 1 + rng.permutation(b * nb).reshape(b, nb).astype(np.int32)

    def pageify(arr, rows):
        arr = np.asarray(arr)
        pool = np.zeros((1 + b * nb, kvh, rows) + arr.shape[3:], arr.dtype)
        for i in range(b):
            for j in range(nb):
                pool[bt[i, j]] = arr[i, :, j * rows:(j + 1) * rows]
        return jnp.asarray(pool)

    qcfg = quest_mod.QuestConfig(page_size=ps, sparsity=sparsity,
                                 sink_tokens=sink, window_tokens=window,
                                 min_pages=min_pages)
    kp = quest_mod.page_budget(qcfg, n // ps, n)
    length = jnp.asarray(lengths, jnp.int32)
    scale = 1 / np.sqrt(hd)
    args = (q, pageify(k_store, bs), pageify(v_store, bs),
            pageify(kmin, bs // ps), pageify(kmax, bs // ps),
            jnp.asarray(bt))
    op_kw = dict(length=length, page_budget=kp, page_size=ps, scale=scale,
                 sink_tokens=sink, window_tokens=window)
    ref_kw = dict(length=length, page_size=ps, sparsity=sparsity,
                  min_pages=min_pages, scale=scale, sink_tokens=sink,
                  window_tokens=window)
    if kv_dtype is not None:
        scales = dict(k_scale=pageify(ks, bs), v_scale=pageify(vs, bs))
        op_kw.update(scales)
        ref_kw.update(scales)
    return args, op_kw, ref_kw


def _build_paged_quest(case):
    args, op_kw, ref_kw = _quest_fixture(**case.kwargs)
    out, sel = paged_quest_attend(*args, with_selection=True, **op_kw)
    ref, sel_ref = paged_quest_attend_ref(*args, **ref_kw)
    return [("attn", out, ref), ("selection", sel, sel_ref, BITWISE)]


def _ring_fixture(seed, b, kvh, g, rb, bs, hd, window, pos, softcap=0.0,
                  dtype=jnp.float32, kv_dtype=None):
    """Circular sliding-window pool: ``rb`` ring blocks per request with
    a shuffled ring slice of the block table and per-request positions
    (both sides read the same pool, so slots outside the window may hold
    arbitrary rows).  ``kv_dtype`` "int8"/"fp8" quantizes the ring pages
    with per-row scale pools alongside."""
    from repro.models.backends import kvquant

    rng = np.random.default_rng(seed)
    pool_k = jnp.asarray(rng.normal(size=(1 + b * rb, kvh, bs, hd)), dtype)
    pool_v = jnp.asarray(rng.normal(size=(1 + b * rb, kvh, bs, hd)), dtype)
    q = jnp.asarray(rng.normal(size=(b, kvh, g, hd)), jnp.float32)
    bt = jnp.asarray(1 + rng.permutation(b * rb).reshape(b, rb), jnp.int32)
    kw = dict(pos=jnp.asarray(pos, jnp.int32), window=window,
              softcap=softcap, scale=1 / np.sqrt(hd))
    if kv_dtype is not None:
        pool_k, ks = kvquant.quantize(pool_k, kv_dtype)
        pool_v, vs = kvquant.quantize(pool_v, kv_dtype)
        kw.update(k_scale=ks, v_scale=vs)
    return (q, pool_k, pool_v, bt), kw


def _build_paged_ring(case):
    args, kw = _ring_fixture(**case.kwargs)
    out = paged_ring_attend(*args, **kw)
    ref = paged_ring_attend_ref(*args, **kw)
    return [("attn", out, ref)]


# --------------------------------------------------- op registry + sweeps

def _c(label, **kw):
    return KernelCase.make(label, **kw)


def _score_case(label, p, l, n, g, bh, d=64, block_n=512, weighted=True):
    return _c(label, p=p, l=l, n=n, g=g, bh=bh, d=d, block_n=block_n,
              weighted=weighted)


def _fd_case(label, bh, g, k, hd, dtype=jnp.float32, block_k=256):
    return _c(label, bh=bh, g=g, k=k, hd=hd, dtype=dtype, block_k=block_k)


def _fp_case(label, bh, s, hd, window, dtype=jnp.float32):
    return _c(label, bh=bh, s=s, hd=hd, window=window, dtype=dtype)


def _pa_case(label, **kw):
    base = dict(seed=0, b=2, kvh=2, g=2, gs=2, nb=4, bs=8, hd=16, p=6,
                l=12, sink=4, window=4, lengths=(13, 29))
    base.update(kw)
    return _c(label, **base)


def _qu_case(label, **kw):
    base = dict(seed=0, b=2, kvh=2, g=2, nb=4, bs=8, hd=16, ps=4,
                sink=4, window=4, lengths=(13, 29))
    base.update(kw)
    return _c(label, **base)


def _ring_case(label, **kw):
    base = dict(seed=0, b=2, kvh=2, g=2, rb=3, bs=8, hd=16, window=10,
                pos=(13, 29), softcap=0.0)
    base.update(kw)
    return _c(label, **base)


KERNEL_OPS = (
    KernelOp(
        name="socket_score",
        build=_build_socket_score,
        policy=ParityPolicy(rtol=1e-5),
        cases=(
            _score_case("paper-point", 10, 60, 1024, 4, 2),
            _score_case("longbench", 8, 60, 512, 1, 2),
            _score_case("wide-planes", 16, 40, 2048, 8, 1),
            _score_case("unaligned-tables", 10, 37, 512, 2, 2),
            _score_case("smoke-scale", 6, 12, 256, 2, 3),
            _score_case("block-128", 10, 60, 1024, 2, 1, d=32,
                        block_n=128, weighted=False),
            _score_case("block-256", 10, 60, 1024, 2, 1, d=32,
                        block_n=256, weighted=False),
            _score_case("ragged-n", 10, 60, 384, 2, 1, block_n=512),
            # packed words: (P, L) = (10, 60) leaves 40 alignment bits
            # (the kernel never reads them); P=8 divides the 32-bit word
            _score_case("paper-point-g1", 10, 60, 1024, 1, 2),
            _score_case("p-divides-word-g4", 8, 60, 1024, 4, 2),
            # N a multiple of the tile: two 1024-key tiles, and one
            # 2048-key tile walked in two 1024-key steps
            _score_case("two-tiles", 10, 60, 2048, 4, 1, block_n=1024),
            _score_case("two-chunks", 10, 60, 2048, 1, 1, block_n=2048),
            # N under one tile and off the 128-key lane width (padded)
            _score_case("n-below-tile", 10, 60, 200, 4, 2),
            # u/tau across the exp range: table log probabilities to about -60
            _c("u-spans-exp", p=10, l=60, n=512, g=4, bh=2, d=64,
               block_n=512, weighted=True, u_scale=10.0),
            # tiles wholly past a row's length are skipped and score 0
            _c("length-skips-tiles", p=10, l=60, n=3072, g=4, bh=2, d=64,
               block_n=1024, weighted=True, lengths=(700, 2100)),
            # words in rows padded to 128 lanes, the pool's bits leaf
            _c("lane-padded-words", p=10, l=60, n=1024, g=4, bh=2, d=64,
               block_n=512, weighted=True, lane_pad=True,
               lengths=(700, 1024)),
            # bits_storage="int8": the kernel streams ±1 plane bytes
            # (no unpack, no padding tables) — same scores as packed
            _c("int8-bits-paper-point", p=10, l=60, n=1024, g=4, bh=2,
               d=64, block_n=512, weighted=True, bits_fmt="int8"),
            _c("int8-bits-unaligned-tables", p=10, l=37, n=512, g=2,
               bh=2, d=64, block_n=512, weighted=True, bits_fmt="int8"),
            _c("int8-bits-block-128", p=6, l=12, n=256, g=2, bh=3,
               d=64, block_n=128, weighted=False, bits_fmt="int8"),
            _c("int8-bits-g1", p=10, l=60, n=512, g=1, bh=2, d=64,
               block_n=512, weighted=True, bits_fmt="int8"),
        ),
    ),
    KernelOp(
        name="flash_decode",
        build=_build_flash_decode,
        policy=ParityPolicy(atol=1e-5, bf16_atol=2e-2),
        cases=(
            _fd_case("f32-1024", 4, 4, 1024, 128),
            _fd_case("bf16-512", 2, 1, 512, 64, dtype=jnp.bfloat16),
            _fd_case("f32-768", 3, 8, 768, 128),
            _fd_case("single-short-block", 2, 2, 100, 32),
            _fd_case("bf16-640", 1, 6, 640, 256, dtype=jnp.bfloat16),
            # non-divisible context lengths: ragged tail blocks exercise
            # the pad-and-mask path across *multiple* K blocks
            _fd_case("tail-300@128", 2, 4, 300, 64, block_k=128),
            _fd_case("tail-100@64", 2, 2, 100, 32, block_k=64),
            _fd_case("bf16-tail-129@64", 1, 6, 129, 64,
                     dtype=jnp.bfloat16, block_k=64),
            _fd_case("len-lt-block", 1, 2, 7, 32, block_k=64),
            _fd_case("tail-515@256", 3, 1, 515, 128),
        ),
    ),
    KernelOp(
        name="flash_prefill",
        build=_build_flash_prefill,
        policy=ParityPolicy(atol=1e-5, bf16_atol=3e-2),
        cases=(
            _fp_case("s512", 2, 512, 64, 0),
            _fp_case("s1024", 2, 1024, 128, 0),
            _fp_case("window-128", 2, 512, 64, 128),
            _fp_case("bf16-window", 1, 256, 128, 64, dtype=jnp.bfloat16),
            _fp_case("non-pow2-seq", 1, 384, 32, 0),
        ),
    ),
    KernelOp(
        name="paged_attention",
        build=_build_paged_attention,
        # attention output under tolerance (logical-order vs rank-order
        # accumulation); the selected set is compared BITWISE per case
        policy=ParityPolicy(atol=2e-5, bf16_atol=2e-2),
        cases=(
            _pa_case("ragged"),
            _pa_case("pooled-short-ctx", seed=1, gs=1, nb=3, g=4,
                     lengths=(24, 5)),
            _pa_case("single-seq", seed=2, b=1, g=1, gs=1, nb=2, bs=16,
                     hd=32, p=8, l=10, sink=2, window=2, lengths=(32,)),
            _pa_case("exact-score-ties", seed=3, b=3, lengths=(1, 17, 32),
                     dup=True),
            _pa_case("unaligned-tables", seed=4, p=10, l=37,
                     lengths=(30, 31)),
            _pa_case("bf16-kv", seed=5, dtype=jnp.bfloat16,
                     lengths=(32, 9)),
            _pa_case("budget-floor", seed=6, sink=8, window=8,
                     lengths=(7, 3)),
            # quantized pool pages: per-row scales dequantized in-kernel;
            # selection stays bitwise (scoring never reads K/V)
            _pa_case("int8-ragged", seed=7, kv_dtype="int8"),
            _pa_case("fp8-ragged", seed=7, kv_dtype="fp8"),
            _pa_case("int8-ties-unaligned-tail", seed=8, b=3,
                     lengths=(1, 17, 30), dup=True, kv_dtype="int8"),
            _pa_case("fp8-unaligned-tables", seed=9, p=10, l=37,
                     lengths=(30, 31), kv_dtype="fp8"),
        ),
    ),
    KernelOp(
        name="paged_hard_lsh",
        build=_build_paged_hard_lsh,
        # same policy split as the socket kernel: float attention under
        # tolerance, the hard-collision selected set BITWISE (collision
        # counts are small integers, so zero-count ties are pervasive —
        # every case exercises the stable tie-break)
        policy=ParityPolicy(atol=2e-5, bf16_atol=2e-2),
        cases=(
            _pa_case("ragged"),
            _pa_case("pooled-hash", seed=1, gs=1, nb=3, g=4,
                     lengths=(24, 5)),
            _pa_case("collision-ties", seed=3, b=3, lengths=(1, 17, 32),
                     dup=True),
            _pa_case("unaligned-tables", seed=4, p=10, l=37,
                     lengths=(30, 31)),
            _pa_case("bf16-kv", seed=5, dtype=jnp.bfloat16,
                     lengths=(32, 9)),
            _pa_case("budget-floor", seed=6, sink=8, window=8,
                     lengths=(7, 3)),
            _pa_case("int8-collision-ties", seed=7, b=3,
                     lengths=(1, 17, 30), dup=True, kv_dtype="int8"),
            _pa_case("fp8-ragged", seed=8, kv_dtype="fp8"),
        ),
    ),
    KernelOp(
        name="paged_quest",
        build=_build_paged_quest,
        policy=ParityPolicy(atol=2e-5, bf16_atol=2e-2),
        cases=(
            _qu_case("ragged-ppb2"),
            _qu_case("page-per-block", seed=1, ps=8, lengths=(24, 5)),
            _qu_case("page-score-ties", seed=3, b=3,
                     lengths=(9, 17, 32), dup=True),
            _qu_case("single-seq", seed=2, b=1, g=1, nb=2, bs=16, ps=4,
                     hd=32, sink=2, window=2, lengths=(32,)),
            _qu_case("bf16-kv", seed=5, dtype=jnp.bfloat16,
                     lengths=(32, 9)),
            _qu_case("budget-floor", seed=6, sink=8, window=8,
                     lengths=(7, 3)),
            # quantized pages + stats from the quantized round trip
            # (quest.stats_from_quantized): selection stays bitwise
            # because kernel and oracle rank the same sound bounds
            _qu_case("int8-ragged", seed=7, kv_dtype="int8"),
            _qu_case("fp8-page-ties-tail", seed=8, b=3,
                     lengths=(9, 17, 30), dup=True, kv_dtype="fp8"),
        ),
    ),
    KernelOp(
        name="paged_ring",
        build=_build_paged_ring,
        policy=ParityPolicy(atol=2e-5, bf16_atol=2e-2),
        cases=(
            _ring_case("wrap-mix"),                    # filling + wrapped
            _ring_case("unwrapped", seed=1, pos=(5, 20)),
            _ring_case("softcap", seed=2, softcap=20.0, pos=(23, 24)),
            _ring_case("window-lt-cap", seed=3, window=6, pos=(100, 7)),
            _ring_case("bf16-kv", seed=4, dtype=jnp.bfloat16,
                       pos=(31, 64)),
            _ring_case("single-block-ring", seed=5, rb=1, window=8,
                       pos=(3, 50)),
            _ring_case("int8-wrap-mix", seed=6, kv_dtype="int8"),
            _ring_case("fp8-softcap-tail", seed=7, softcap=20.0,
                       pos=(23, 11), kv_dtype="fp8"),
        ),
    ),
)

_PAIRS, _IDS = all_cases(KERNEL_OPS)


@pytest.mark.parametrize("op,case", _PAIRS, ids=_IDS)
def test_kernel_matches_oracle(op, case):
    """Differential sweep: every kernel op == its ref.py oracle under the
    op's declared parity policy."""
    run_differential(op, case)


# ----------------------------------------------- fused selection property

def _selection_case(seed, b, nb, lengths, gs, sink, window, dup=False):
    """Kernel selection vs the reference value_aware_topk selection."""
    kvh, g = 2, 2
    args, kw, kq = _paged_fixture(
        seed=seed, b=b, kvh=kvh, g=g, gs=gs, nb=nb, bs=8, hd=16, p=6, l=12,
        sink=sink, window=window, lengths=lengths, dup=dup)
    _, sel = paged_socket_attend(*args, with_selection=True, **kw)
    _, sel_ref = paged_socket_attend_ref(*args, top_k=kq, **kw)
    return np.asarray(sel), np.asarray(sel_ref), kw


@pytest.mark.parametrize("seed,b,nb,lengths,gs,sink,window,dup", [
    (10, 2, 4, (13, 29), 2, 4, 4, False),     # ragged mid-context
    (11, 2, 3, (24, 5), 1, 4, 4, False),      # pooled + ctx < sink+window
    (12, 3, 4, (1, 17, 32), 2, 4, 4, True),   # exact score ties
    (13, 1, 2, (16,), 1, 8, 8, False),        # everything forced
    (14, 2, 4, (32, 31), 2, 0, 4, False),     # no sinks, window only
])
def test_fused_selection_matches_reference(seed, b, nb, lengths, gs, sink,
                                           window, dup):
    """The fused kernel's selected set must equal the reference
    ``socket_attend`` selection (value_aware_topk) exactly: sink+window
    forcing, ragged lengths, budget floors, holes in the block table."""
    sel, sel_ref, kw = _selection_case(seed, b, nb, lengths, gs, sink,
                                       window, dup)
    np.testing.assert_array_equal(sel, sel_ref)
    # sanity on the semantics themselves, not just parity
    for i, ln in enumerate(lengths):
        assert not sel[i, :, ln:].any(), "selected past the live length"
        forced = min(ln, sink + window)
        per_head = sel[i].sum(axis=-1)
        assert (per_head >= min(forced, int(kw["budget"][i]))).all(), \
            "budget floor must keep the forced sink+window set selected"


@given(data=st.data())
@settings(deadline=None)   # example count / derandomization come from the
def test_fused_selection_property(data):   # profile pinned in conftest.py
    """Hypothesis sweep of the same contract over random geometries:
    random block tables with holes (shuffled physical pages), ragged
    lengths including contexts shorter than sink+window (the PR-1
    budget-floor regression case)."""
    seed = data.draw(st.integers(0, 2**16), label="seed")
    b = data.draw(st.integers(1, 3), label="batch")
    nb = data.draw(st.integers(1, 4), label="blocks_per_seq")
    gs = data.draw(st.sampled_from([1, 2]), label="score_groups")
    sink = data.draw(st.integers(0, 8), label="sink")
    window = data.draw(st.integers(0, 8), label="window")
    n = nb * 8
    lengths = tuple(
        data.draw(st.integers(1, n), label=f"len{i}") for i in range(b))
    dup = data.draw(st.booleans(), label="duplicate_keys")
    sel, sel_ref, _ = _selection_case(seed, b, nb, lengths, gs, sink,
                                      window, dup)
    np.testing.assert_array_equal(sel, sel_ref)


# ------------------------------------------------------- special regressions

def test_flash_decode_all_masked_rows_are_finite():
    """A fully-masked (empty-selection) row must not produce NaNs."""
    q = jnp.ones((1, 2, 32))
    k = jnp.ones((1, 64, 32))
    v = jnp.ones((1, 64, 32))
    mask = jnp.zeros((1, 64), bool)
    out = flash_decode(q, k, v, mask, scale=0.1, block_k=64)
    assert bool(jnp.all(jnp.isfinite(out)))


def test_flash_decode_raw_launcher_pads_tail():
    """The raw Pallas launcher (not the padding ``ops.py`` wrapper) must
    accept ``K % block_k != 0`` and ``K < block_k`` — it used to raise a
    trace-time ValueError, so any caller bypassing the wrapper (or a
    wrapper regression) broke on ragged selection widths."""
    from repro.kernels.flash_decode.flash_decode import flash_decode_pallas
    for seed, (bh, g, k, hd, blk) in enumerate(
            ((2, 4, 70, 32, 32),      # tail block: 70 % 32 != 0
             (1, 2, 13, 32, 64))):    # whole buffer shorter than block_k
        k1, k2, k3, k4 = jax.random.split(jax.random.PRNGKey(seed), 4)
        q = jax.random.normal(k1, (bh, g, hd))
        kk = jax.random.normal(k2, (bh, k, hd))
        vv = jax.random.normal(k3, (bh, k, hd))
        mask = jax.random.bernoulli(k4, 0.7, (bh, k)).at[:, 0].set(True)
        out = flash_decode_pallas(q, kk, vv, mask, scale=1 / np.sqrt(hd),
                                  block_k=blk, interpret=True)
        ref = flash_decode_ref(q, kk, vv, mask, scale=1 / np.sqrt(hd))
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5)


def test_paged_attention_rejects_bad_packing():
    """The fused kernel must fail fast when the packed width cannot be
    viewed as whole tables of P planes (hashing.num_words pads to make
    this divisible — a hand-rolled 3-word layout with P=7 cannot be)."""
    nb, bs, hd, p, l = 2, 8, 16, 7, 10       # 3 words = 96 bits, 96 % 7 != 0
    q = jnp.zeros((1, 1, 1, hd))
    kv = jnp.zeros((3, 1, bs, hd))
    bits = jnp.zeros((3, 1, bs, 3), jnp.uint32)
    vn = jnp.zeros((3, 1, bs))
    u = jnp.zeros((1, 1, 1, l, p))
    bt = jnp.asarray([[1, 2]], jnp.int32)
    with pytest.raises(ValueError, match="not a multiple"):
        paged_socket_attend(q, kv, kv, bits, vn, u, bt, length=9, budget=4,
                            num_tables=l, num_planes=p, tau=0.4, scale=0.25,
                            sink_tokens=2, window_tokens=2)


def test_flash_prefill_matches_model_attention(rng):
    """Kernel == the model's XLA attention path (same math)."""
    from repro.configs import get_config
    from repro.models import attention as attn
    from repro.models import param as pm

    cfg = get_config("minitron-8b").smoke()
    params = pm.unbox(attn.init_attention(cfg, rng))
    b, t = 2, 64
    x = jax.random.normal(jax.random.fold_in(rng, 1), (b, t, cfg.d_model))
    positions = jnp.broadcast_to(jnp.arange(t), (b, t))
    y_model = attn.attention_train(cfg, params, x, positions, "global")
    assert y_model.shape == (b, t, cfg.d_model)
    assert bool(jnp.all(jnp.isfinite(y_model)))
