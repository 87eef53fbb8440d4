"""Serving launcher.

Two engines:

* ``--engine static`` (legacy): prefill one fixed-shape batch, decode in
  lockstep, report throughput.
* ``--engine continuous``: the paged-KV continuous-batching engine
  (repro.serving) fed Poisson-arriving requests of mixed prompt lengths;
  reports throughput, TTFT and p50/p99 per-token latency.

    PYTHONPATH=src python -m repro.launch.serve --arch stablelm-12b \
        --smoke --engine continuous --backend socket

    PYTHONPATH=src python -m repro.launch.serve --arch stablelm-12b \
        --smoke --batch 4 --prompt-len 256 --decode-steps 64 \
        --backend socket
"""

from __future__ import annotations

import argparse
import os
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.models import param as pm
from repro.models import transformer as tfm
from repro.runtime.steps import make_prefill_step, make_serve_step
# strict JSON: NaN/Infinity serialized as null, never the non-strict
# tokens (an empty-series percentile is NaN; json.dumps would happily
# emit `NaN`, which no compliant parser accepts)
from repro.serving.obs.events import strict_dumps

# serving-surface backend names: the real DecodeBackend registry plus the
# *_fused pseudo-backends (backend + its cfg.*.use_paged_kernel gate — the
# fused Pallas paged-attention passes, PagedView/continuous-engine only)
SERVING_BACKENDS = ("socket", "socket_fused", "dense", "quest",
                    "quest_fused", "hard_lsh", "hard_lsh_fused")


def apply_backend_arg(cfg, backend: str):
    """Resolve a serving-surface backend name onto the config.  Shared by
    this CLI and ``benchmarks.bench_serving`` so the pseudo-backend
    mapping lives in exactly one place."""
    import dataclasses
    if backend in ("socket_fused", "hard_lsh_fused"):
        # hard_lsh shares SOCKET's cache layout and kernel gate
        return cfg.replace(
            attention_backend=backend[: -len("_fused")],
            socket=dataclasses.replace(cfg.socket, use_paged_kernel=True))
    if backend == "quest_fused":
        return cfg.replace(
            attention_backend="quest",
            quest=dataclasses.replace(cfg.quest, use_paged_kernel=True))
    return cfg.replace(attention_backend=backend)


# K/V pool page storage modes (ServingSettings.kv_dtype): "auto" stores
# pages at the compute dtype, int8/fp8 quantize on write with per-row
# absmax scales and dequantize in-kernel on the fused paths
KV_DTYPES = ("auto", "bf16", "int8", "fp8")


def apply_kv_dtype(cfg, kv_dtype):
    """Resolve a ``--kv-dtype`` value onto the config's serving plan.
    Shared by this CLI and ``benchmarks.bench_serving`` so the
    quantized-pool knob lives in exactly one place.  ``None`` keeps the
    config's own ``serving.kv_dtype``; the dtype matrix itself (fp8
    needs the fused kernels, quest needs quantized-round-trip stats,
    ...) is enforced by ``cfg.validate()``."""
    if kv_dtype is None:
        return cfg
    if kv_dtype not in KV_DTYPES:
        raise ValueError(f"kv_dtype={kv_dtype!r} not in {KV_DTYPES}")
    return cfg.replace(serving=cfg.serving.replace(kv_dtype=kv_dtype))


def configure_compile_cache(repo_root: Path) -> str:
    """Persistent compile cache location for an entry point's process.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is overridden.  Otherwise the cache goes to the fixed
    ``<repo_root>/.jax_cache`` (git-ignored): the directory is part of
    the cache key, so a path built from a temporary name, a process id
    or the time would never hit.  Call from ``main()``, never on import.
    Returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(Path(repo_root).resolve() / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def run_serve(cfg, batch: int, prompt_len: int, decode_steps: int,
              seed: int = 0, prompt=None):
    """Prefill + greedy decode; returns (tokens, prefill_s, decode_s).

    ``prompt``: optional (batch, prompt_len) int32 token array — the
    parity tests feed the same prompts to both engines.
    """
    rng = jax.random.PRNGKey(seed)
    params = pm.unbox(tfm.init_model(cfg, rng))
    capacity = prompt_len + decode_steps
    if cfg.input_mode == "tokens":
        if prompt is None:
            prompt = jax.random.randint(rng, (batch, prompt_len), 0,
                                        cfg.vocab_size)
        batch_in = {"tokens": jnp.asarray(prompt, jnp.int32)}
    else:
        batch_in = {"embeds": jax.random.normal(
            rng, (batch, prompt_len, cfg.d_model),
            jnp.dtype(cfg.compute_dtype))}

    prefill = jax.jit(make_prefill_step(cfg, capacity))
    serve = jax.jit(make_serve_step(cfg))

    t0 = time.time()
    logits, caches = prefill(params, batch_in)
    logits.block_until_ready()
    prefill_s = time.time() - t0

    toks = [jnp.argmax(logits[:, -1], axis=-1)[:, None]]
    # warm up compile outside the timed loop
    _, caches_w = serve(params, caches, toks[-1] if cfg.input_mode ==
                        "tokens" else jax.random.normal(
                            rng, (batch, 1, cfg.d_model)),
                        jnp.int32(prompt_len))
    del caches_w

    t0 = time.time()
    for t in range(decode_steps):
        inp = toks[-1] if cfg.input_mode == "tokens" else \
            jax.random.normal(jax.random.fold_in(rng, t),
                              (batch, 1, cfg.d_model))
        logits, caches = serve(params, caches, inp,
                               jnp.int32(prompt_len + t))
        toks.append(jnp.argmax(logits[:, -1], axis=-1)[:, None])
    toks[-1].block_until_ready()
    decode_s = time.time() - t0
    return jnp.concatenate(toks, axis=1), prefill_s, decode_s


def make_poisson_requests(cfg, num_requests: int, rate_rps: float,
                          prompt_lens, max_new_tokens: int, seed: int = 0):
    """Poisson arrival process with prompt lengths drawn from
    ``prompt_lens`` (the multi-tenant mixed-length regime)."""
    from repro.serving import Request
    rng = np.random.default_rng(seed)
    t = 0.0
    reqs = []
    for _ in range(num_requests):
        t += float(rng.exponential(1.0 / rate_rps))
        plen = int(rng.choice(prompt_lens))
        prompt = rng.integers(0, cfg.vocab_size, size=plen,
                              dtype=np.int64).tolist()
        reqs.append(Request(prompt=prompt, max_new_tokens=max_new_tokens,
                            arrival=t))
    return reqs


def serving_ceiling(cfg) -> int:
    """Largest servable prompt+generated context: the block table alone
    under chunked prefill, additionally the largest prefill bucket in
    legacy whole-prompt mode."""
    sv = cfg.serving
    if sv.prefill_chunk:
        return sv.max_context
    return min(max(sv.prefill_buckets), sv.max_context)


def run_continuous(cfg, num_requests: int, rate_rps: float, prompt_lens,
                   max_new_tokens: int, seed: int = 0, realtime=True,
                   warmup=False, temperature: float = 0.0,
                   top_p: float = 1.0, arrivals=None, obs=None,
                   prompts=None, params=None):
    """Continuous-batching serve; returns (requests, ServeMetrics,
    engine) — the engine exposes the run's metrics registry
    (``engine.registry``) for snapshot / Prometheus exposition.

    ``warmup=True`` pre-compiles the shapes this workload needs (chunked
    mode: the mixed + decode steps; legacy: only the buckets the prompts
    hit) so the reported TTFT/latency reflect steady-state serving, not
    jit.  ``temperature > 0`` samples inside the jitted decode step
    (temperature + nucleus top-p, per-request seeded PRNG); the default
    is greedy, bit-exact vs the static engine.  ``arrivals``: optional
    explicit per-request arrival times overriding the Poisson draw
    (cycled over ``prompt_lens`` in order).  ``obs``: optional
    :class:`repro.serving.obs.Observability` bundle (event trace /
    selection probe / profiler) threaded into the engine.
    ``prompts``: optional explicit token lists (e.g. from
    :mod:`repro.serving.prefix_cache.workloads`) overriding the random
    draw — the prefix-cache workloads need real shared prefixes, which
    independent random prompts never have; ``prompt_lens`` is ignored.
    ``params``: optional model weights (default: initialized from
    ``seed``), so several runs can share one copy on the device.
    """
    from repro.serving.engine import ContinuousBatchingEngine
    engine = ContinuousBatchingEngine(cfg, params=params,
                                      rng=jax.random.PRNGKey(seed),
                                      temperature=temperature, top_p=top_p,
                                      sample_seed=seed, obs=obs)
    if prompts is not None:
        from repro.serving import Request
        assert len(prompts) == num_requests, (
            f"prompts ({len(prompts)}) must match num_requests "
            f"({num_requests})")
        if arrivals is None:
            rng = np.random.default_rng(seed)
            t, arrivals = 0.0, []
            for _ in range(num_requests):
                t += float(rng.exponential(1.0 / rate_rps))
                arrivals.append(t)
        reqs = [Request(prompt=list(p), max_new_tokens=max_new_tokens,
                        arrival=t) for p, t in zip(prompts, arrivals)]
    elif arrivals is None:
        reqs = make_poisson_requests(cfg, num_requests, rate_rps,
                                     prompt_lens, max_new_tokens, seed=seed)
    else:
        from repro.serving import Request
        assert len(arrivals) == num_requests, (
            f"arrivals ({len(arrivals)}) must match num_requests "
            f"({num_requests})")
        rng = np.random.default_rng(seed)
        reqs = [Request(prompt=rng.integers(
                    0, cfg.vocab_size,
                    size=prompt_lens[i % len(prompt_lens)]).tolist(),
                        max_new_tokens=max_new_tokens, arrival=t)
                for i, t in enumerate(arrivals)]
    if warmup:
        engine.warmup(reqs)
    metrics = engine.run(reqs, realtime=realtime)
    return reqs, metrics, engine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--engine", default="static",
                    choices=["static", "continuous"])
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=256)
    ap.add_argument("--decode-steps", type=int, default=64)
    ap.add_argument("--backend", default="socket",
                    choices=list(SERVING_BACKENDS),
                    help="decode backend; the *_fused names route the "
                         "continuous engine through the corresponding "
                         "fused Pallas paged-attention kernel")
    ap.add_argument("--kv-dtype", default=None, choices=list(KV_DTYPES),
                    help="K/V pool page storage: 'auto' (compute dtype), "
                         "'bf16', or quantized 'int8'/'fp8' pages with "
                         "per-row scales dequantized in-kernel (default: "
                         "the config's serving.kv_dtype)")
    ap.add_argument("--ring-kernel", action="store_true",
                    help="route sliding-window (local) layer decode "
                         "through the Pallas ring kernel (continuous "
                         "engine; no-op for all-global architectures)")
    # continuous-engine knobs
    ap.add_argument("--num-requests", type=int, default=8)
    ap.add_argument("--rate", type=float, default=20.0,
                    help="Poisson arrival rate (requests/s)")
    ap.add_argument("--max-new-tokens", type=int, default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="sampling temperature; 0 = greedy (default)")
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="nucleus sampling mass (with --temperature > 0)")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="chunked-prefill token budget per engine "
                         "iteration (continuous engine; 0 = legacy "
                         "whole-prompt bucketed prefill; default: the "
                         "config's serving.prefill_chunk)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="enable the radix-tree prefix cache (continuous "
                         "engine, chunked prefill, all-paged plans; "
                         "hybrid/ring plans fall back to no sharing)")
    ap.add_argument("--workload", default="mixed",
                    choices=["mixed", "chatbot", "rag"],
                    help="request generator: 'mixed' = independent "
                         "random prompts of mixed lengths (default); "
                         "'chatbot' = multi-turn sessions whose prompts "
                         "share growing histories; 'rag' = shared "
                         "template + unique suffix")
    ap.add_argument("--overlap", type=float, default=0.6,
                    help="shared-template fraction of each prompt "
                         "(--workload rag)")
    ap.add_argument("--sessions", type=int, default=2,
                    help="number of concurrent chat sessions "
                         "(--workload chatbot)")
    # observability (continuous engine)
    ap.add_argument("--trace", default=None, metavar="FILE",
                    help="stream a schema-validated JSONL event trace "
                         "of the run to FILE")
    ap.add_argument("--perfetto", default=None, metavar="FILE",
                    help="also export the trace as Chrome trace-event "
                         "JSON (open at https://ui.perfetto.dev); "
                         "requires --trace")
    ap.add_argument("--metrics-json", default=None, metavar="FILE",
                    help="write the run's metrics-registry snapshot as "
                         "strict JSON")
    ap.add_argument("--metrics-prom", default=None, metavar="FILE",
                    help="write the run's metrics registry in "
                         "Prometheus text exposition format")
    ap.add_argument("--probe-every", type=int, default=0,
                    help="sample the SOCKET selection-quality probe "
                         "every N engine iterations (0 = off; socket "
                         "backend, kvhead/pooled selection)")
    ap.add_argument("--profile-dir", default=None,
                    help="capture a jax.profiler trace of the engine "
                         "loop into this directory")
    ap.add_argument("--profile-steps", type=int, default=20,
                    help="profiled window length in engine iterations "
                         "(with --profile-dir)")
    args = ap.parse_args()
    configure_compile_cache(Path(__file__).resolve().parents[3])

    if args.backend.endswith("_fused") and args.engine != "continuous":
        ap.error(f"--backend {args.backend} requires --engine continuous: "
                 "the fused kernels serve the paged decode path only "
                 "(the static engine would silently run the unfused "
                 "backend)")
    if args.ring_kernel and args.engine != "continuous":
        ap.error("--ring-kernel requires --engine continuous: the ring "
                 "kernel streams the paged pool's circular page lists")
    if args.temperature > 0 and args.engine != "continuous":
        ap.error("--temperature requires --engine continuous: sampling "
                 "lives in the continuous engine's jitted decode step "
                 "(the static engine would silently decode greedily)")
    if not 0.0 < args.top_p <= 1.0:
        ap.error(f"--top-p must be in (0, 1], got {args.top_p}")

    if args.prefill_chunk is not None and args.engine != "continuous":
        ap.error("--prefill-chunk requires --engine continuous: chunked "
                 "prefill is the continuous engine's execution model")
    if args.prefix_cache and args.engine != "continuous":
        ap.error("--prefix-cache requires --engine continuous: the "
                 "prefix cache shares pages of the continuous engine's "
                 "paged pool")
    if args.workload != "mixed" and args.engine != "continuous":
        ap.error("--workload chatbot/rag requires --engine continuous")
    if not 0.0 <= args.overlap < 1.0:
        ap.error(f"--overlap must be in [0, 1), got {args.overlap}")
    obs_flags = (args.trace, args.perfetto, args.metrics_json,
                 args.metrics_prom, args.profile_dir)
    if (any(f is not None for f in obs_flags) or args.probe_every) \
            and args.engine != "continuous":
        ap.error("observability flags (--trace/--perfetto/--metrics-*/"
                 "--probe-every/--profile-dir) require --engine "
                 "continuous")
    if args.perfetto and not args.trace:
        ap.error("--perfetto needs --trace (it exports the event trace)")

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.smoke()
    cfg = apply_backend_arg(cfg, args.backend)
    cfg = apply_kv_dtype(cfg, args.kv_dtype)
    if args.ring_kernel:
        cfg = cfg.replace(use_ring_kernel=True)
    if args.prefill_chunk is not None:
        cfg = cfg.replace(serving=cfg.serving.replace(
            prefill_chunk=args.prefill_chunk))
    if args.prefix_cache:
        cfg = cfg.replace(serving=cfg.serving.replace(prefix_cache=True))

    if args.engine == "continuous":
        sv = cfg.serving
        # mixed prompt lengths, bounded so prompt+generated fits the
        # serving ceiling (block table only when chunked; additionally
        # the largest prefill bucket in legacy whole-prompt mode)
        max_new = args.max_new_tokens or (8 if args.smoke else 64)
        ceiling = serving_ceiling(cfg)
        top = ceiling - max_new
        if top < 1:
            ap.error(f"--max-new-tokens {max_new} leaves no prompt room "
                     f"under the serving context ceiling "
                     f"({ceiling} tokens)")
        lens = sorted({max(1, top // 4), max(1, top // 2),
                       max(1, (3 * top) // 4), top})
        prompts = None
        if args.workload == "chatbot":
            from repro.serving.prefix_cache.workloads import chatbot_prompts
            prompts = chatbot_prompts(args.num_requests,
                                      sessions=args.sessions,
                                      max_prompt_len=top,
                                      vocab_size=cfg.vocab_size,
                                      seed=args.seed)
        elif args.workload == "rag":
            from repro.serving.prefix_cache.workloads import rag_prompts
            prompts = rag_prompts(args.num_requests, prompt_len=top,
                                  overlap=args.overlap,
                                  vocab_size=cfg.vocab_size,
                                  seed=args.seed)
        obs = None
        if any(f is not None for f in obs_flags) or args.probe_every:
            from repro.serving.obs import Observability
            obs = Observability(args.trace, probe_every=args.probe_every,
                                profile_dir=args.profile_dir,
                                profile_steps=args.profile_steps)
        reqs, m, engine = run_continuous(cfg, args.num_requests,
                                         args.rate, lens,
                                         max_new, seed=args.seed,
                                         temperature=args.temperature,
                                         top_p=args.top_p, obs=obs,
                                         prompts=prompts)
        report = {
            "arch": cfg.name, "backend": args.backend,
            "engine": "continuous",
            "kv_dtype": sv.kv_dtype,
            "prefill_chunk": sv.prefill_chunk,
            "workload": args.workload,
            "prompt_lens": lens if prompts is None else sorted(
                {len(p) for p in prompts}),
            "max_new_tokens": max_new,
            "temperature": args.temperature,
            "top_p": args.top_p,
            "finished": sum(r.state == "finished" for r in reqs),
            **m.to_json(),
        }
        if args.prefix_cache:
            reg = engine.registry
            hits = reg.value("prefix_cache_hits_total")
            misses = reg.value("prefix_cache_misses_total")
            report["prefix_cache"] = {
                # engine.prefix_cache is None when the plan can't share
                # (hybrid/ring/legacy prefill) — the flag degrades to a
                # no-op and this block records that honestly
                "active": engine.prefix_cache is not None,
                "hits": hits, "misses": misses,
                "hit_rate": hits / (hits + misses) if hits + misses
                else None,
                "cached_tokens": reg.value(
                    "prefix_cache_cached_tokens_total"),
                "prompt_tokens": reg.value(
                    "prefix_cache_prompt_tokens_total"),
                "cow_copies": reg.value("prefix_cache_cow_total"),
                "evicted_blocks": reg.value(
                    "prefix_cache_evicted_total"),
            }
        if obs is not None:
            obs.close()
            if args.probe_every:
                report["probe"] = obs.probe_summary()
            if args.perfetto:
                from repro.serving.obs import write_chrome_trace
                write_chrome_trace(args.trace, args.perfetto)
            if args.metrics_json:
                with open(args.metrics_json, "w") as f:
                    f.write(strict_dumps(engine.registry.snapshot(),
                                         indent=2, sort_keys=True))
            if args.metrics_prom:
                with open(args.metrics_prom, "w") as f:
                    f.write(engine.registry.prometheus_text())
        print(strict_dumps(report, indent=2))
        return

    toks, prefill_s, decode_s = run_serve(cfg, args.batch, args.prompt_len,
                                          args.decode_steps,
                                          seed=args.seed)
    tput = args.batch * args.decode_steps / decode_s
    print(strict_dumps({
        "arch": cfg.name, "backend": args.backend, "engine": "static",
        "prefill_s": round(prefill_s, 3),
        "decode_s": round(decode_s, 3),
        "decode_tokens_per_s": round(tput, 1),
        "generated_shape": list(toks.shape),
    }, indent=2))


if __name__ == "__main__":
    main()
