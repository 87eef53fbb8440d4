"""One-chip smoke run of the served path on a TPU.

Serves minitron-8b at its published widths (d_model 4096, 32 q / 8 KV
heads, head_dim 128, d_ff 16384, vocab 256000) in bf16, cut to 8 of its
32 layers, through the continuous-batching engine — once on the XLA
SOCKET decode path (``socket``) and once on the fused Pallas kernel
(``socket_fused``), compiled by Mosaic — after checking that kernel
against its pure-jnp oracle on a seeded pool at the same widths.

    python chip_smoke.py

Everything runs in this one process (a chip belongs to one process at a
time).  Each phase prints one JSON line; the last line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``.
The script exits 2 before printing any result when JAX's first device
is not a TPU, and 1 when any phase fails its gate.  The compile cache
goes where ``JAX_COMPILATION_CACHE_DIR`` says, else to ``.jax_cache/``
beside this file.  Times and memory it prints are one chip run, not
benchmark results.
"""

from __future__ import annotations

import gc
import json
import math
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

ROOT = Path(__file__).resolve().parent

ARCH = "minitron-8b"
LAYERS = 8                       # of 32: whole layers, so weights + pool fit
BLOCK_SIZE = 16
BLOCKS_PER_SEQ = 512             # 8,192-token context ceiling
MIN_POOL_TOKENS = 32768
NUM_REQUESTS = 8
PROMPT_LENS = tuple(int(x) for x in np.linspace(3000, 7000, NUM_REQUESTS))
NEW_TOKENS = 32
# Pool sizing, from compiling the engine's steps for a described v5e at
# three pool sizes: the largest step (the XLA path's mixed step) needs
# ~2.3 GiB of temporaries whatever the pool, and each pool block costs
# ~3.9x its own bytes at peak, because the layer scan writes a second,
# lane-padded copy of the pool.
RESERVE_BYTES = 3 << 30
POOL_PEAK_FACTOR = 4.0
SEED = 0

# Kernel phase tolerance.  Kernel and oracle both run f32 attention over
# the same bf16 K/V rows (HIGHEST-precision matmuls) and differ only in
# accumulation order and the device's exp, which moves an output by
# ~1e-6; 1e-4 + 1e-4·|ref| leaves two orders of margin while one key
# wrongly kept or dropped moves it by ~1/budget (>= 1e-3 here).
KERNEL_ATOL = 1e-4
KERNEL_RTOL = 1e-4
# A key whose score sits within an ulp of the radix threshold can flip
# between two f32 implementations: such rows are counted, not compared,
# and at least this share of (request, head) rows must select exactly.
MIN_EXACT_ROWS = 0.9


def device_info() -> dict:
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def _emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


# ------------------------------------------------------------------ kernel

def kernel_phase(*, batch: int = 8, kv_heads: int = 8, group: int = 4,
                 head_dim: int = 128, block_size: int = BLOCK_SIZE,
                 blocks_per_seq: int = BLOCKS_PER_SEQ, num_planes: int = 10,
                 num_tables: int = 60, sparsity: float = 10.0,
                 sink: int = 128, window: int = 128, min_k: int = 16,
                 tau: float = 0.4, seed: int = SEED,
                 interpret=None) -> dict:
    """``paged_socket_attend`` on a seeded random pool vs
    ``paged_socket_attend_ref`` at HIGHEST matmul precision.

    Defaults are the engine phase's widths (minitron-8b, P=10, L=60,
    16-token pages, 512-entry block tables, bf16 pages, ragged lengths
    from the full 8,192-token table down).  ``interpret=None`` compiles
    with Mosaic on a TPU and interprets elsewhere."""
    from repro.core import hashing
    from repro.core import socket as sk
    from repro.kernels.common import resolve_interpret
    from repro.kernels.paged_attention import (paged_socket_attend,
                                               paged_socket_attend_ref)

    n = blocks_per_seq * block_size
    nblocks = 1 + batch * blocks_per_seq          # block 0 = trash
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    w = hashing.make_hash_params(ks[0], head_dim, num_planes, num_tables)
    shape = (nblocks, kv_heads, block_size, head_dim)
    k_pages = jax.random.normal(ks[1], shape, jnp.bfloat16)
    v_pages = jax.random.normal(ks[2], shape, jnp.bfloat16)
    bits = hashing.pack_signs(hashing.hash_keys_signs(
        w, k_pages.astype(jnp.float32)))
    vnorm = jnp.linalg.norm(v_pages.astype(jnp.float32),
                            axis=-1).astype(jnp.bfloat16)
    q = jax.random.normal(ks[3], (batch, kv_heads, group, head_dim))
    u = sk.soft_hash_query(w, q)
    rng = np.random.default_rng(seed)
    bt = jnp.asarray(1 + rng.permutation(batch * blocks_per_seq)
                     .reshape(batch, blocks_per_seq), jnp.int32)
    length = jnp.asarray(np.linspace(n, max(1, n // 16), batch).astype(int),
                         jnp.int32)
    scfg = sk.SocketConfig(num_planes=num_planes, num_tables=num_tables,
                           tau=tau, sparsity=sparsity, sink_tokens=sink,
                           window_tokens=window, min_k=min_k)
    top_k = sk.topk_budget(scfg, n)
    kw = dict(length=length,
              budget=sk.dynamic_topk_budget(scfg, length, top_k),
              num_tables=num_tables, num_planes=num_planes, tau=tau,
              scale=1.0 / math.sqrt(head_dim), sink_tokens=sink,
              window_tokens=window)
    args = (q, k_pages, v_pages, bits, vnorm, u, bt)

    out = paged_socket_attend(*args, interpret=interpret, **kw)
    out_s, sel = paged_socket_attend(*args, interpret=interpret,
                                     with_selection=True, **kw)
    with jax.default_matmul_precision("highest"):
        ref, sel_ref = paged_socket_attend_ref(*args, top_k=top_k, **kw)
    out, out_s, ref = (np.asarray(x, np.float64) for x in (out, out_s, ref))
    sel, sel_ref = np.asarray(sel), np.asarray(sel_ref)

    flipped = (sel != sel_ref).sum(axis=-1)                 # (B, KVH)
    exact = flipped == 0
    err = np.abs(out - ref)
    bound = KERNEL_ATOL + KERNEL_RTOL * np.abs(ref)
    rows_ok = (err <= bound).all(axis=(-2, -1))             # (B, KVH)
    report = {
        "phase": "kernel", "op": "paged_socket_attend",
        "interpret": resolve_interpret(interpret),
        "shape": {"batch": batch, "kv_heads": kv_heads, "group": group,
                  "head_dim": head_dim, "block_size": block_size,
                  "blocks_per_seq": blocks_per_seq, "planes": num_planes,
                  "tables": num_tables},
        "atol": KERNEL_ATOL, "rtol": KERNEL_RTOL,
        "rows_exact_selection": float(exact.mean()),
        "flipped_keys": int(flipped.sum()),
        "selected_keys": int(sel_ref.sum()),
        "max_abs_err_exact_rows": float(err[exact].max()) if exact.any()
        else None,
        "max_abs_err_all_rows": float(err.max()),
        "with_selection_max_diff": float(np.abs(out - out_s).max()),
        "finite": bool(np.isfinite(out).all()),
    }
    report["ok"] = bool(
        report["finite"] and exact.mean() >= MIN_EXACT_ROWS
        and (flipped <= 1).all() and rows_ok[exact].all()
        and report["with_selection_max_diff"] == 0.0)
    return report


# ------------------------------------------------------------------ engine

def engine_config(num_blocks: int = 2):
    """minitron-8b at published widths, bf16, cut to ``LAYERS`` whole
    layers, with the smoke run's serving geometry."""
    from repro.configs import get_config
    cfg = get_config(ARCH)
    return cfg.replace(
        num_groups=LAYERS, param_dtype="bfloat16",
        compute_dtype="bfloat16",
        serving=cfg.serving.replace(
            block_size=BLOCK_SIZE, max_blocks_per_seq=BLOCKS_PER_SEQ,
            num_blocks=num_blocks, max_batch=NUM_REQUESTS))


def init_params(cfg, seed: int = SEED):
    """Random weights made on the device in one jitted program."""
    from repro.models import param as pm
    from repro.models import transformer as tfm
    return jax.jit(lambda k: pm.unbox(tfm.init_model(cfg, k)))(
        jax.random.PRNGKey(seed))


def pool_blocks(cfg) -> int:
    """Pool blocks that fit in what the weights (already resident) leave,
    less ``RESERVE_BYTES``, at ``POOL_PEAK_FACTOR`` times a block's
    bytes."""
    from repro.serving import paged
    stats = jax.devices()[0].memory_stats()
    per_block = paged.pool_block_bytes(cfg)["per_block_id"]
    free = stats["bytes_limit"] - stats["bytes_in_use"] - RESERVE_BYTES
    return int(free // (per_block * POOL_PEAK_FACTOR))


def make_prompts(vocab_size: int, lens=PROMPT_LENS, seed: int = SEED):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab_size, size=n).tolist() for n in lens]


def _decode_step_text(engine) -> str:
    """Compiled text of the engine's decode-only step at its own
    argument shapes (served from the in-process compile cache)."""
    sv = engine.serving
    tokens = jnp.zeros((sv.max_batch, 1), jnp.int32)
    bt = jnp.zeros((sv.max_batch, sv.max_blocks_per_seq), jnp.int32)
    pos = jnp.zeros((sv.max_batch,), jnp.int32)
    active = jnp.zeros((sv.max_batch,), bool)
    return engine._decode_fn.lower(engine.params, engine.pages,
                                   engine._keys, tokens, bt, pos,
                                   active).compile().as_text()


def _release(engine) -> None:
    """Free the engine's pool now (the engine's jitted closures keep it
    alive until a garbage collection otherwise)."""
    for leaf in jax.tree_util.tree_leaves(engine.pages):
        leaf.delete()
    engine.pages = None
    gc.collect()


def engine_phase(cfg, params, prompts, *, new_tokens: int = NEW_TOKENS,
                 seed: int = SEED, expect_kernel: bool = True) -> dict:
    """Serve ``prompts`` through ``run_continuous(..., warmup=True)``
    with the XLA ``socket`` path and with ``socket_fused``; gate on
    finished in-vocab generations, equal first tokens (prefill is shared)
    and, with ``expect_kernel``, a Mosaic kernel in the fused decode
    step."""
    from repro.launch.serve import apply_backend_arg, run_continuous

    runs = {}
    for backend in ("socket", "socket_fused"):
        bcfg = apply_backend_arg(cfg, backend)
        t0 = time.perf_counter()
        reqs, m, engine = run_continuous(
            bcfg, len(prompts), rate_rps=1.0, prompt_lens=None,
            max_new_tokens=new_tokens, seed=seed, warmup=True,
            arrivals=[0.0] * len(prompts), prompts=prompts, params=params)
        total_s = time.perf_counter() - t0
        text = _decode_step_text(engine) if backend == "socket_fused" \
            else ""
        stats = jax.devices()[0].memory_stats() or {}
        runs[backend] = {
            "tokens": [list(r.generated) for r in reqs],
            "finished": [r.state == "finished" for r in reqs],
            "setup_s": total_s - m.wall_s,
            "wall_s": m.wall_s,
            "ttft_s_mean": m.ttft_s_mean,
            "ttft_s_p99": m.ttft_s_p99,
            "tokens_per_s": m.throughput_tok_s,
            "token_latency_s_p50": m.token_latency_s_p50,
            "decode_iters": m.decode_iters,
            "prefill_chunks": m.prefill_chunks,
            "preemptions": m.preemptions,
            "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
            "tpu_custom_call": "tpu_custom_call" in text,
        }
        _release(engine)
        del reqs, engine

    vocab = cfg.vocab_size
    gates = {}
    for name, r in runs.items():
        gates[f"{name}_complete"] = all(r["finished"]) and all(
            len(t) == new_tokens and all(0 <= x < vocab for x in t)
            for t in r["tokens"])
    firsts = [[t[0] if t else None for t in r["tokens"]]
              for r in runs.values()]
    gates["same_first_token"] = firsts[0] == firsts[1]
    if expect_kernel:
        gates["fused_step_has_kernel"] = runs["socket_fused"][
            "tpu_custom_call"]
    a, b = (np.asarray(r["tokens"]) for r in runs.values())
    report = {
        "phase": "engine",
        # setup_s = engine construction + warmup compiles; every time and
        # byte count here is from this one run
        "measured": "one chip run, not a benchmark result",
        "arch": cfg.name, "layers": cfg.num_layers,
        "d_model": cfg.d_model, "heads": cfg.num_heads,
        "kv_heads": cfg.num_kv_heads, "head_dim": cfg.head_dim,
        "d_ff": cfg.d_ff, "vocab": cfg.vocab_size,
        "dtype": cfg.compute_dtype,
        "pool_blocks": cfg.serving.num_blocks,
        "block_size": cfg.serving.block_size,
        "max_blocks_per_seq": cfg.serving.max_blocks_per_seq,
        "prompt_lens": [len(p) for p in prompts],
        "new_tokens": new_tokens,
        "gates": gates,
        "decode_token_agreement": float((a == b).mean())
        if a.shape == b.shape else None,
        "runs": {k: {kk: vv for kk, vv in v.items()
                     if kk not in ("tokens", "finished")}
                 for k, v in runs.items()},
    }
    report["ok"] = all(gates.values())
    return report


# -------------------------------------------------------------------- main

def main() -> int:
    info = device_info()
    if info["platform"] != "tpu":
        print(f"chip_smoke: needs a TPU; JAX's first device is "
              f"{info['platform']!r} ({info['kind']})", file=sys.stderr)
        return 2
    if str(ROOT / "src") not in sys.path:
        sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.serve import configure_compile_cache
    _emit({"phase": "setup", "device": info,
           "compile_cache": configure_compile_cache(ROOT)})

    ok = True
    report = kernel_phase(interpret=False)
    _emit(report)
    ok &= report["ok"]
    gc.collect()

    t0 = time.perf_counter()
    cfg = engine_config()
    params = init_params(cfg)
    jax.block_until_ready(params)
    weight_bytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(params))
    blocks = pool_blocks(cfg)
    if (blocks - 1) * BLOCK_SIZE < MIN_POOL_TOKENS:
        print(f"chip_smoke: only {blocks} pool blocks fit beside "
              f"{weight_bytes} weight bytes", file=sys.stderr)
        return 1
    cfg = engine_config(num_blocks=blocks)
    _emit({"phase": "weights", "bytes": weight_bytes,
           "init_s": time.perf_counter() - t0, "pool_blocks": blocks,
           "pool_tokens": (blocks - 1) * BLOCK_SIZE})
    report = engine_phase(cfg, params, make_prompts(cfg.vocab_size))
    _emit(report)
    ok &= report["ok"]

    if not ok:
        print("chip_smoke: a phase failed its gate", file=sys.stderr)
        return 1
    _emit({"ok": True, "device": info})
    return 0


if __name__ == "__main__":
    sys.exit(main())
