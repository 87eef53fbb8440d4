"""Continuous-batching serving engine (sglang/vLLM-style, JAX-static).

Execution model — the **token-budget mixed step** (default,
``serving.prefill_chunk > 0``): each iteration the scheduler grants at
most ONE fixed-size prefill chunk (``PrefillChunk`` cursor on the
request, per-chunk block growth) alongside the full ragged decode batch,
and a single jitted call runs both.  Chunk queries attend over the pages
earlier chunks committed (prefix-extension attention — see
:func:`repro.models.attention.attention_prefill_chunk`), sliding-window
rings thread the chunk through the circular page list, and Mamba state
carries across chunks in the per-slot state rows.  Consequences:

* decode stall per iteration is bounded by one chunk, not one prompt —
  no head-of-line blocking on long-context prefills;
* exactly TWO compiles total (mixed step + decode-only step) instead of
  one per prefill bucket;
* prompts are bounded only by ``max_blocks_per_seq * block_size``, not
  by the largest prefill bucket.

``serving.prefill_chunk == 0`` keeps the legacy alternating phases:
whole-prompt bucket-padded prefill (one compile per bucket, prompts
beyond the largest bucket rejected), then one ragged decode step.

Layers are cached per the **per-layer cache plan** (``cfg.cache_plan()``):
global-attention layers hold backend-paged KV (+ SOCKET bits / Quest
stats) addressed linearly by the block table; sliding-window layers a
bounded circular page ring; Mamba layers O(1) per-slot state holding no
blocks at all.  Heterogeneous layouts (gemma3's 5:1 local:global,
jamba's attn:mamba hybrid, pure-SSM mamba2) all serve continuously.

For **paged-capable** backends (``DecodeBackend.supports_paged``: socket,
hard_lsh, quest) — or models without global-attention layers — the
decode step hands the page pool + block tables straight to the model:
appends write to pages in place and global attention reads only the
small metadata leaves plus the selected ``O(top_k)`` K/V rows — no
contiguous K/V view is ever materialized.  Otherwise (dense) the engine
falls back to the gather/scatter round trip (``paged.gather_views`` /
``scatter_token``), which is still window-bounded for ring layers and
free for state layers.

Sampling is greedy by default (bit-exact vs the static engine);
``temperature > 0`` switches the jitted step to temperature + top-p
sampling.  Each request owns its PRNG key (folded from the engine seed
and the request's submission index, stored on the ``Request`` and
re-installed into the slot on every admission), and slot key streams
only advance while their request is active — a request's sample stream
is a pure function of (seed, submission index, token index), so
preemption resume replays sampled generations bit-exactly and batch
composition never perturbs a request's randomness.
``input_mode == "tokens"`` only.
"""

from __future__ import annotations

import dataclasses
import math
import time
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import backends as bk
from repro.models import param as pm
from repro.models import transformer as tfm
from repro.runtime.steps import (make_chunk_prefill_step, make_prefill_step,
                                 make_serve_step)
from repro.serving import paged, sampling
from repro.serving.block_pool import TRASH_BLOCK, BlockPool
from repro.serving.obs import Observability
from repro.serving.obs.metrics import Registry
from repro.serving.obs.profiling import null_span
from repro.serving.prefix_cache import PrefixCache
from repro.serving.scheduler import (PREFILL, PrefillChunk, Request,
                                     Scheduler)

__all__ = ["ContinuousBatchingEngine", "ServeMetrics"]


@dataclasses.dataclass
class ServeMetrics:
    """Aggregate serving metrics for one engine run."""

    num_requests: int
    total_generated: int
    wall_s: float
    throughput_tok_s: float
    ttft_s_mean: float
    ttft_s_p99: float
    # percentiles of the gaps between consecutive tokens of one request
    token_latency_s_p50: float
    token_latency_s_p99: float
    preemptions: int
    decode_iters: int
    prefill_chunks: int
    # longest wall-clock gap between consecutive token emissions of any
    # single request — the head-of-line-blocking metric chunked prefill
    # exists to bound (legacy mode: a long co-tenant prompt lands here)
    intertoken_stall_s_max: float
    # p99 over jitted step-call durations (mixed or decode-only)
    decode_iter_s_p99: float

    def to_json(self) -> Dict:
        """Strict-JSON dict: non-finite floats (empty-series percentiles
        are NaN) become ``None``/``null`` — ``NaN`` is not JSON and a
        default ``json.dump`` of it breaks every compliant consumer."""
        out = {}
        for k, v in dataclasses.asdict(self).items():
            if isinstance(v, float):
                v = round(v, 6) if math.isfinite(v) else None
            out[k] = v
        return out


class ContinuousBatchingEngine:
    """Paged-cache continuous batching over one model replica."""

    def __init__(self, cfg: ModelConfig, params=None,
                 rng: Optional[jax.Array] = None, *,
                 temperature: float = 0.0, top_p: float = 1.0,
                 sample_seed: int = 0,
                 obs: Optional[Observability] = None):
        self._validate(cfg)
        self.cfg = cfg
        self.serving = cfg.serving
        self.serving.validate()
        if params is None:
            rng = rng if rng is not None else jax.random.PRNGKey(0)
            params = pm.unbox(tfm.init_model(cfg, rng))
        self.params = params
        self.backend = bk.get_backend(cfg.attention_backend)
        plan = cfg.cache_plan()
        has_paged = any(p.kind == "paged" for p in plan)
        ring_blocks = max((p.ring_blocks for p in plan
                           if p.kind == "ring"), default=0)
        self._has_state = any(p.kind == "state" for p in plan)
        # page-native decode: paged-capable backend, or no global layer
        # consumes the backend at all (ring/state layers are page-native
        # by construction)
        self._paged_native = self.backend.supports_paged or not has_paged
        self.temperature = float(temperature)
        self.top_p = float(top_p)
        self._sample_base = jax.random.PRNGKey(sample_seed)
        self._submitted = 0
        self._keys = sampling.slot_keys(sample_seed, self.serving.max_batch)
        self.pages = paged.init_paged_caches(cfg, self.serving)
        self.pool = BlockPool(self.serving.num_blocks)
        self.scheduler = Scheduler(
            self.pool, max_batch=self.serving.max_batch,
            max_blocks_per_seq=self.serving.max_blocks_per_seq,
            block_size=self.serving.block_size,
            has_paged_layers=has_paged, ring_blocks=ring_blocks,
            prefill_chunk=self.serving.prefill_chunk)
        self._decode_fn = self._build_decode()
        self._mixed_fn = self._build_mixed() if self.chunked else None
        self._prefilling: Optional[Request] = None
        self._prefill_fns: Dict[int, callable] = {}
        # ---- prefix cache ------------------------------------------------
        # Cross-request page reuse is valid exactly when (a) chunked
        # prefill is on (a hit IS a prefill starting at a nonzero
        # cursor — legacy bucketed prefill has no cursor), and (b) every
        # layer is paged: ring layers recycle their block-table prefix
        # circularly (a "shared prefix" would be rewritten in place) and
        # Mamba state is per-slot, not per-page, so a cached prefix
        # would resume with the wrong recurrent state.  Hybrids fall
        # back to no-share cleanly — the flag stays on, no cache is
        # built, serving is unchanged.
        self.prefix_cache = None
        self._cow_fn = None
        if self.serving.prefix_cache and self.chunked and has_paged \
                and ring_blocks == 0 and not self._has_state:
            spec = self.backend.cache_spec(cfg)
            # granularity>1 leaves (Quest per-page stats) summarize every
            # row of a page: partial-page sharing or a partial CoW keep
            # would score junk keys, so such plans share page-aligned
            # prefixes only (and structurally never hit the CoW path).
            tail_ok = all(s.granularity == 1 for s in spec.values())
            self.prefix_cache = PrefixCache(
                self.pool, block_size=self.serving.block_size,
                tail_shareable=tail_ok)
            self.scheduler.prefix_cache = self.prefix_cache

            def _clone(pages, src, dst, keep):
                return paged.clone_block(self.cfg, pages, src, dst, keep)

            self._cow_fn = jax.jit(_clone, donate_argnums=(0,))
        # test hook: called as iter_hook(engine, iteration) at the end of
        # every engine iteration (CoW invariant property tests snapshot
        # shared pages here); None in production.
        self.iter_hook = None
        # (iteration, rid, chunk.start, chunk.tokens) per chunk co-run —
        # lets tests pin "never more than one chunk per decode iteration"
        self.chunk_trace: List[Tuple[int, int, int, int]] = []
        # ---- observability ----------------------------------------------
        # The metrics registry is always on (pure-Python counters; the
        # end-of-run ServeMetrics derive from it).  Everything else —
        # tracer, probe, profiler — only exists when ``obs`` is given:
        # with obs=None the hot loop allocates zero tracing objects per
        # step (pinned by tests/test_observability.py).
        self.obs = obs
        self.registry = Registry()
        self._bind_instruments(self.registry)
        self._probe_fn = None
        self._compile_seen: set = set()
        self._probe_capable = (
            (cfg.attention_backend in ("hard_lsh", "quest")
             or (cfg.attention_backend == "socket"
                 and cfg.socket.selection in ("kvhead", "pooled")))
            and has_paged)
        if obs is not None:
            counts = paged.cache_kind_counts(cfg)
            obs.tracer.ensure_start(
                arch=cfg.name, backend=cfg.attention_backend,
                prefill_chunk=self.serving.prefill_chunk,
                layers_paged=counts["paged"], layers_ring=counts["ring"],
                layers_state=counts["state"],
                prefix_cache=self.prefix_cache is not None)

    @property
    def chunked(self) -> bool:
        return self.serving.prefill_chunk > 0

    # ------------------------------------------------------ observability
    def _bind_instruments(self, reg: Registry) -> None:
        """Create (or re-bind, at run start) the run-scoped serving
        series.  ``exact=True``: these histograms also retain samples,
        so end-of-run ServeMetrics percentiles are byte-identical to a
        direct ``np.percentile`` over the recorded series."""
        self._c_tokens = reg.counter("serve_tokens_total")
        self._h_ttft = reg.histogram("serve_ttft_s", exact=True)
        self._h_stall = reg.histogram("serve_intertoken_stall_s",
                                      exact=True)
        self._h_iter = reg.histogram("serve_iter_s", exact=True)

    def _set_gauges(self, reg: Registry) -> None:
        """Pool and batch gauges, set once when a run ends."""
        st = self.pool.stats()
        reg.gauge("pool_blocks_free").set(st["free"])
        reg.gauge("pool_blocks_used").set(st["used"])
        reg.gauge("pool_blocks_high_water").set(st["high_water"])
        sched = self.scheduler
        reg.gauge("batch_running").set(len(sched.running))
        reg.gauge("batch_prefilling").set(len(sched.prefilling))
        reg.gauge("batch_waiting").set(len(sched.waiting))
        if self.prefix_cache is not None:
            reg.gauge("prefix_cache_shared_blocks").set(
                self.prefix_cache.shared_blocks)
            reg.gauge("prefix_cache_evictable_blocks").set(
                self.prefix_cache.evictable_blocks())

    def _note_call(self, tag: str, seconds: float) -> None:
        """First dispatch of a jitted shape = trace + compile + run;
        record it as a compile event so latency analysis can discount
        it (warmup marks the shapes it covers)."""
        if tag in self._compile_seen:
            return
        self._compile_seen.add(tag)
        if self.obs is not None:
            self.obs.tracer.emit("compile", fn=tag,
                                 seconds=round(seconds, 6))

    def _note_token(self, req: Request, w: float) -> None:
        """Per-emitted-token bookkeeping shared by the decode loop and
        the first-token prefill sites."""
        self._c_tokens.inc()
        req.token_walls.append(w)
        if len(req.token_walls) >= 2:
            self._h_stall.record(req.token_walls[-1]
                                 - req.token_walls[-2])

    def _note_first_token(self, req: Request, t: float) -> None:
        req.t_first_token = t
        ttft = t - req.arrival
        self._h_ttft.record(ttft)
        if self.obs is not None:
            self.obs.tracer.emit("first_token", rid=req.rid,
                                 ttft_s=round(ttft, 6))

    @staticmethod
    def _validate(cfg: ModelConfig) -> None:
        if cfg.input_mode != "tokens":
            raise NotImplementedError(
                "continuous engine serves token models only")
        if any(s.kind == "attn" and s.attn_type == "global"
               for s in cfg.layer_specs):
            # resolves the backend (ValueError on unknown names) and
            # validates its cache layout against the serving geometry
            # (e.g. quest's page_size must divide block_size)
            bk.get_backend(cfg.attention_backend).cache_spec(cfg)
        if cfg.decode_cp_axes:
            raise NotImplementedError(
                "ragged decode + context-parallel SOCKET is a ROADMAP item")

    # --------------------------------------------------------------- jit
    @jax.named_scope("model.head")
    def _pick(self, logits: jax.Array, keys: jax.Array):
        """Next-token choice from one step's ``(B, 1, V)`` logits."""
        last = logits[:, -1]
        if self.temperature > 0:
            return sampling.sample_tokens(
                last, keys, temperature=self.temperature, top_p=self.top_p,
                vocab_size=self.cfg.vocab_size)
        return jnp.argmax(last, axis=-1), keys

    def _decode_body(self, serve, params, pages, keys, tokens, bt, pos,
                     active):
        """Shared ragged-decode body of the decode-only and mixed steps.

        ``active`` (``(B,)`` bool) marks slots holding a runnable
        request: inactive slots keep their per-slot state rows (a
        chunk-owner's Mamba state must survive the decode iterations
        between its chunks) and their PRNG keys (a request's sample
        stream advances exactly once per emitted token, never while the
        slot idles — the replay-exact resume invariant).
        """
        if self._paged_native:
            # page-native path: the pool + block tables go straight into
            # the model; no contiguous K/V view is ever materialized.
            logits, new_pages = serve(params, pages, tokens, pos, bt)
        else:
            views = paged.gather_views(self.cfg, pages, bt)
            logits, views = serve(params, views, tokens, pos)
            new_pages = paged.scatter_token(self.cfg, pages, views, bt, pos)
        if self._has_state:
            new_pages = paged.keep_state_rows(self.cfg, pages, new_pages,
                                              active)
        tok, new_keys = self._pick(logits, keys)
        keys = jnp.where(active[:, None], new_keys, keys)
        return tok, keys, new_pages

    def _build_decode(self):
        serve = make_serve_step(self.cfg)

        def step(params, pages, keys, tokens, bt, pos, active):
            return self._decode_body(serve, params, pages, keys, tokens,
                                     bt, pos, active)

        return jax.jit(step, donate_argnums=(1,))

    def _build_mixed(self):
        """The token-budget mixed step: one prefill chunk + the full
        ragged decode batch in ONE jitted call.  The chunk runs first
        (its writes land in blocks disjoint from every decoding
        request), then the decode batch; ``ch_final`` gates whether the
        chunk's logits consume the slot's PRNG key (only the final chunk
        emits a token)."""
        serve = make_serve_step(self.cfg)
        chunk_fn = make_chunk_prefill_step(self.cfg)

        def step(params, pages, keys, ch_tokens, ch_bt, ch_slot, ch_hist,
                 ch_last, ch_final, tokens, bt, pos, active):
            logits_c, pages = chunk_fn(params, pages, ch_tokens, ch_bt,
                                       ch_slot, ch_hist, ch_last)
            tok_c, key_c = self._pick(logits_c, keys[ch_slot][None])
            keys = keys.at[ch_slot].set(
                jnp.where(ch_final, key_c[0], keys[ch_slot]))
            tok, keys, pages = self._decode_body(
                serve, params, pages, keys, tokens, bt, pos, active)
            return tok_c[0], tok, keys, pages

        return jax.jit(step, donate_argnums=(1,))

    def _bt_row_len(self, bucket: int) -> int:
        """Prefill block-table row length: the bucket's blocks, but at
        least the circular window pages (a short prompt's ring still
        spans ``ring_blocks`` table entries; unallocated ones are
        trash)."""
        return max(bucket // self.serving.block_size,
                   self.scheduler.ring_blocks)

    def _chunk_bt_len(self) -> int:
        """Chunk block-table row length: the full per-request table plus
        one chunk of slack, so the final (padded) chunk's block window
        never clamps — its overhang entries are trash."""
        sv = self.serving
        return sv.max_blocks_per_seq + sv.prefill_chunk // sv.block_size

    def _prefill_fn(self, bucket: int):
        if bucket not in self._prefill_fns:
            prefill = make_prefill_step(self.cfg, bucket, bucketed=True,
                                        paged=True)

            def step(params, pages, keys, tokens, last_index, bt_row,
                     slot):
                logits, caches = prefill(params, {"tokens": tokens},
                                         last_index)
                pages = paged.write_prefill(self.cfg, pages, caches,
                                            bt_row, slot)
                tok, key = self._pick(logits, keys[slot][None])
                keys = keys.at[slot].set(key[0])
                return tok, keys, pages

            self._prefill_fns[bucket] = jax.jit(step, donate_argnums=(1,))
        return self._prefill_fns[bucket]

    def warmup(self, requests: Optional[List[Request]] = None) -> None:
        """Trigger the jit compiles a run will need against the trash
        page, so a subsequent run's TTFT and latency percentiles measure
        serving, not compilation.  Chunked mode needs exactly TWO shapes
        (mixed + decode-only) regardless of the workload; legacy mode
        warms one prefill compile per bucket — only the buckets
        ``requests`` will actually hit when given, all of them otherwise.
        Sampling keys are not consumed (warmup randomness is
        discarded)."""
        sv = self.serving
        tokens = jnp.zeros((sv.max_batch, 1), jnp.int32)
        bt = jnp.full((sv.max_batch, sv.max_blocks_per_seq), TRASH_BLOCK,
                      jnp.int32)
        pos = jnp.zeros((sv.max_batch,), jnp.int32)
        active = jnp.zeros((sv.max_batch,), bool)
        t_w = time.perf_counter()
        _, _, self.pages = self._decode_fn(self.params, self.pages,
                                           self._keys, tokens, bt, pos,
                                           active)
        self._note_call("decode", time.perf_counter() - t_w)
        if self.chunked:
            ch_bt = jnp.full((self._chunk_bt_len(),), TRASH_BLOCK,
                             jnp.int32)
            t_w = time.perf_counter()
            _, _, _, self.pages = self._mixed_fn(
                self.params, self.pages, self._keys,
                jnp.zeros((1, sv.prefill_chunk), jnp.int32), ch_bt,
                jnp.int32(0), jnp.int32(0), jnp.zeros((1,), jnp.int32),
                jnp.asarray(False), tokens, bt, pos, active)
            self._note_call("mixed", time.perf_counter() - t_w)
            if self._cow_fn is not None:
                # clone trash onto trash: compiles the CoW kernel without
                # touching any real page (keep=0 scrubs block 0, whose
                # contents are never read unmasked anyway)
                t_w = time.perf_counter()
                self.pages = self._cow_fn(self.pages, jnp.int32(0),
                                          jnp.int32(0), jnp.int32(0))
                self._note_call("cow_clone", time.perf_counter() - t_w)
            return
        buckets = sv.prefill_buckets if requests is None else sorted(
            {self._bucket_for(len(r.prefill_tokens)) for r in requests})
        for bucket in buckets:
            bt_row = jnp.full((self._bt_row_len(bucket),), TRASH_BLOCK,
                              jnp.int32)
            t_w = time.perf_counter()
            _, _, self.pages = self._prefill_fn(bucket)(
                self.params, self.pages, self._keys,
                jnp.zeros((1, bucket), jnp.int32),
                jnp.zeros((1,), jnp.int32), bt_row, jnp.int32(0))
            self._note_call(f"prefill_{bucket}",
                            time.perf_counter() - t_w)

    def _bucket_for(self, n: int) -> int:
        for b in sorted(self.serving.prefill_buckets):
            if b >= n:
                return b
        raise ValueError(
            f"prompt of {n} tokens exceeds largest prefill bucket "
            f"{max(self.serving.prefill_buckets)} (chunked prefill — "
            f"serving.prefill_chunk > 0 — serves prompts up to "
            f"max_context {self.serving.max_context})")

    # -------------------------------------------------------------- keys
    def _register(self, req: Request) -> None:
        """Assign the request's sampling key at first submission: folded
        from the engine seed and the submission index, so the stream is
        deterministic per workload and survives preemption (re-submission
        keeps the stored key)."""
        if req.sample_key is None:
            req.sample_key = np.asarray(
                jax.random.fold_in(self._sample_base, self._submitted))
        self._submitted += 1

    def _install_key(self, req: Request) -> None:
        """(Re-)install the request's key into its slot at admission.
        Replay after preemption then re-advances the stream exactly as
        the original run did — one consumption per emitted token."""
        keys = np.array(self._keys)          # writable host copy
        keys[req.slot] = req.sample_key
        self._keys = jnp.asarray(keys)

    # -------------------------------------------------------------- run
    def run(self, requests: List[Request],
            realtime: bool = True) -> ServeMetrics:
        """Serve ``requests`` (arrival times in seconds relative to run
        start) to completion.  ``realtime=False`` treats arrivals as
        already-arrived (offline batch; deterministic, used by tests)."""
        sched = self.scheduler
        obs = self.obs
        tracer = obs.tracer if obs is not None else None
        probe = obs.probe if obs is not None and obs.probe.every > 0 \
            and self._probe_capable else None
        profiler = obs.profiler if obs is not None else None
        span = profiler.annotate if profiler is not None else null_span
        reg = self.registry = Registry()    # run-scoped, like the metrics
        self._bind_instruments(reg)
        sched.bind_obs(reg, tracer)
        self.chunk_trace = []               # per-run, like the metrics
        run_ord = tracer.begin_run(requests=len(requests)) if tracer \
            else 0
        for r in requests:
            self._register(r)
            sched.submit(r)
            if tracer:
                tracer.emit("submit", rid=r.rid,
                            prompt_tokens=len(r.prompt),
                            max_new_tokens=r.max_new_tokens,
                            arrival=float(r.arrival))
        t0 = time.perf_counter()
        wall = lambda: time.perf_counter() - t0
        now = wall if realtime else (lambda: float("inf"))
        stamp = wall if realtime else (lambda: 0.0)
        decode_iters = 0
        c_iters_mixed = reg.counter("serve_iters_total", kind="mixed")
        c_iters_decode = reg.counter("serve_iters_total", kind="decode")
        c_chunks = reg.counter("serve_chunks_total")
        t_synced = None             # the previous step's token on the host

        while sched.has_work:
            if profiler is not None:
                profiler.maybe_start(decode_iters, tracer)
            with span("engine.schedule"):
                runnable, chunk = self._schedule(now, stamp, wall)
            if not runnable and chunk is None:
                if sched.waiting and not sched.running and \
                        self._prefilling is None:
                    nxt = min(r.arrival for r in sched.waiting)
                    wait = nxt - now()
                    if realtime and wait > 0:
                        time.sleep(min(wait, 0.05))
                continue
            # ---------------- ragged decode (+ chunk) -------------------
            t_tables = time.perf_counter()
            with span("engine.tables"):
                tokens, bt, pos, active = self._tables(runnable)
            if probe is not None and runnable \
                    and probe.due(decode_iters):
                with span("engine.probe"):
                    self._run_probe(decode_iters, tokens, bt, pos, active,
                                    runnable)
            kind = "decode" if chunk is None else "mixed"
            t_dispatch = time.perf_counter()
            with span(kind):
                if chunk is not None:
                    first_tok, next_tok = self._run_mixed(
                        chunk, tokens, bt, pos, active)
                else:
                    next_tok, self._keys, self.pages = self._decode_fn(
                        self.params, self.pages, self._keys,
                        jnp.asarray(tokens), jnp.asarray(bt),
                        jnp.asarray(pos), jnp.asarray(active))
            t_sync = time.perf_counter()
            with span("engine.sync"):
                next_tok = np.asarray(next_tok)
            t_prev, t_synced = t_synced, time.perf_counter()
            with span("engine.emit"):
                it_s = t_synced - t_tables
                self._note_call(kind, it_s)
                self._h_iter.record(it_s)
                if chunk is not None:
                    self.chunk_trace.append((decode_iters,
                                             self._prefilling.rid,
                                             chunk.start, chunk.tokens))
                    c_chunks.inc()
                    self._finish_chunk(chunk, first_tok, wall, stamp)
                    c_iters_mixed.inc()
                else:
                    c_iters_decode.inc()
                for r in runnable:
                    # post-preemption replay: steps whose output token is
                    # already recorded only rebuild the cache — the
                    # recomputation is identical, so the produced token is
                    # discarded, not re-sampled (token-exact resume).
                    replaying = r.pos - len(r.prompt) + 1 < len(r.generated)
                    if not replaying:
                        r.generated.append(int(next_tok[r.slot]))
                        self._note_token(r, wall())
                    r.pos += 1
                    if r.done and not replaying:
                        sched.finish(r, stamp())
                if tracer:
                    pool = self.pool
                    tracer.emit(
                        "step", t=t_dispatch, iter=decode_iters, kind=kind,
                        occupancy=int(active.sum()),
                        chunk_tokens=chunk.tokens if chunk is not None
                        else 0,
                        step_s=round(it_s, 6), pool_free=pool.num_free,
                        pool_used=pool.num_used,
                        pool_high_water=pool.high_water,
                        waiting=len(sched.waiting),
                        prefilling=len(sched.prefilling),
                        running=len(sched.running),
                        host_s=None if t_prev is None
                        else round(t_sync - t_prev, 6))
                decode_iters += 1
                if self.iter_hook is not None:
                    self.iter_hook(self, decode_iters)
            if profiler is not None:
                profiler.maybe_stop(decode_iters, tracer)

        if profiler is not None:
            profiler.stop(tracer)           # run shorter than the window
        self._set_gauges(reg)
        wall_total = time.perf_counter() - t0
        m = self._metrics(requests, wall_total)
        if tracer:
            tracer.end_run(run_ord, requests=len(requests),
                           generated=m.total_generated,
                           wall_s=round(wall_total, 6))
        return m

    def _schedule(self, now, stamp, wall
                  ) -> Tuple[List[Request], Optional[PrefillChunk]]:
        """One iteration's host scheduling: decode-table growth, CoW,
        admission and the chunk grant (chunked mode), or whole-prompt
        prefills then growth (legacy mode).  Returns the runnable decode
        batch and the granted chunk (None when none was granted)."""
        sched = self.scheduler
        chunk: Optional[PrefillChunk] = None
        if not self.chunked:
            # legacy order: whole-prompt prefill phase, then growth — a
            # request admitted this iteration decodes this iteration
            # (ensure-first would cost every admission one extra
            # iteration of inter-token latency)
            for _ in range(self.serving.max_prefill_per_iter):
                req = sched.try_admit(now())
                if req is None:
                    break
                self._install_key(req)
                self._prefill_one(req, wall)
                if req.t_first_token is None:
                    self._note_first_token(req, stamp())
                sched.activate(req)
                if req.done:          # max_new_tokens == 1 degenerate case
                    sched.finish(req, stamp())
            return sched.ensure_decode_blocks(), None
        # decode-table growth FIRST (it may evict the prefiller, which
        # must not happen after a chunk has been granted — the granted
        # chunk's block ids would be dangling)...
        runnable = sched.ensure_decode_blocks()
        if self.prefix_cache is not None:
            self._resolve_decode_cow(runnable)
        if self._prefilling is not None and \
                self._prefilling.state != PREFILL:
            self._prefilling = None      # evicted by decode growth/CoW
        # ...then the chunk grant (alloc-only — its cache-evict tier frees
        # refcount-1 pages, never a live request — so it cannot invalidate
        # the runnable snapshot)
        if self._prefilling is None:
            req = sched.try_admit(now())
            if req is not None:
                self._install_key(req)
                self._prefilling = req
        if self._prefilling is not None:
            chunk = sched.grant_chunk(self._prefilling)
            if chunk is None and self._prefilling.state != PREFILL:
                self._prefilling = None       # safety self-preempt
        if chunk is not None and self.prefix_cache is not None \
                and not self._resolve_chunk_cow(self._prefilling, chunk):
            # the prefiller itself was preempted making room for its CoW
            # clone — the granted chunk is void
            chunk = None
            self._prefilling = None
        if self.prefix_cache is not None:
            # CoW allocation may have LRU-preempted decoders out of the
            # snapshot taken above
            runnable = [r for r in runnable
                        if sched.running.get(r.slot) is r]
        return runnable, chunk

    def _tables(self, runnable: List[Request]):
        """The decode batch's host tables: ``(tokens, block tables,
        positions, active)`` numpy arrays, one row per slot."""
        sv = self.serving
        tokens = np.zeros((sv.max_batch, 1), np.int32)
        bt = np.full((sv.max_batch, sv.max_blocks_per_seq), TRASH_BLOCK,
                     np.int32)
        pos = np.zeros((sv.max_batch,), np.int32)
        active = np.zeros((sv.max_batch,), bool)
        for r in runnable:
            tokens[r.slot, 0] = r.input_token(r.pos)
            bt[r.slot, :len(r.blocks)] = r.blocks
            pos[r.slot] = r.pos
            active[r.slot] = True
        return tokens, bt, pos, active

    # --------------------------------------------------------------- cow
    def _cow(self, req: Request, idx: int, keep: int) -> bool:
        """Un-share block ``idx`` of ``req`` before a write: allocate a
        fresh block (cache-evict, then LRU-preempt tiers), device-clone
        the page's first ``keep`` token rows across every paged leaf —
        scrubbing the rest to init fill, so the donor's tokens past the
        matched prefix (or its generated continuation) never leak into
        the new owner — and swap it into the request's table.  The old
        block is deref'd, never mutated: the CoW invariant.  Returns
        False iff ``req`` itself was preempted to make room."""
        old = req.blocks[idx]
        new = self.scheduler.cow_alloc(req)
        if new is None:
            return False
        t_c = time.perf_counter()
        self.pages = self._cow_fn(self.pages, jnp.int32(old),
                                  jnp.int32(new), jnp.int32(keep))
        self._note_call("cow_clone", time.perf_counter() - t_c)
        req.blocks[idx] = new
        self.pool.free([old])
        self.registry.counter("prefix_cache_cow_total").inc()
        if self.obs is not None:
            self.obs.tracer.emit("cow_copy", rid=req.rid, block=old,
                                 clone=new, keep_tokens=keep)
        return True

    def _resolve_chunk_cow(self, req: Request,
                           chunk: PrefillChunk) -> bool:
        """Clone any shared block the granted chunk would write.  Only
        the chunk's FIRST block can be shared — a cache hit's cursor may
        sit mid-way through the matched tail page — but every touched
        block is checked (cheap, and keeps the invariant local).  Returns
        False iff ``req`` was preempted while allocating a clone."""
        bs = self.serving.block_size
        first = chunk.start // bs
        last = (chunk.start + chunk.tokens - 1) // bs
        for idx in range(first, min(last + 1, len(req.blocks))):
            if self.pool.is_shared(req.blocks[idx]):
                if not self._cow(req, idx,
                                 keep=max(0, chunk.start - idx * bs)):
                    return False
        return True

    def _resolve_decode_cow(self, runnable: List[Request]) -> None:
        """Enforce the CoW invariant for the decode batch.  Structurally
        a decode write position (``pos >= prompt_len``) can never sit in
        a shared page — the cache indexes prompt-pure pages only, and a
        hit's tail page is un-shared by the first chunk write — but the
        invariant is cheap to enforce locally rather than by global
        argument, and it stays correct under future insert policies."""
        bs = self.serving.block_size
        for r in runnable:
            idx = r.pos // bs
            if idx < len(r.blocks) and self.pool.is_shared(r.blocks[idx]):
                self._cow(r, idx, keep=r.pos % bs)

    # ------------------------------------------------------------- chunk
    def _run_mixed(self, chunk: PrefillChunk, tokens, bt, pos, active):
        """Dispatch the mixed step for ``chunk`` plus the decode batch."""
        req = self._prefilling
        sv = self.serving
        c = sv.prefill_chunk
        ch_tokens = np.zeros((1, c), np.int32)
        ch_tokens[0, :chunk.tokens] = \
            req.prefill_tokens[chunk.start:chunk.start + chunk.tokens]
        ch_bt = np.full((self._chunk_bt_len(),), TRASH_BLOCK, np.int32)
        ch_bt[:len(req.blocks)] = req.blocks
        first_tok, next_tok, self._keys, self.pages = self._mixed_fn(
            self.params, self.pages, self._keys, jnp.asarray(ch_tokens),
            jnp.asarray(ch_bt), jnp.int32(req.slot), jnp.int32(chunk.start),
            jnp.asarray([chunk.tokens - 1], jnp.int32),
            jnp.asarray(chunk.final), jnp.asarray(tokens), jnp.asarray(bt),
            jnp.asarray(pos), jnp.asarray(active))
        return first_tok, next_tok

    def _finish_chunk(self, chunk: PrefillChunk, first_tok, wall,
                      stamp) -> None:
        """Advance the cursor; on the final chunk record the first token
        (unless replay already holds it) and activate into decode."""
        req = self._prefilling
        sched = self.scheduler
        sched.advance_chunk(req, chunk)
        if not chunk.final:
            return
        if not req.generated:
            req.generated.append(int(np.asarray(first_tok)))
            self._note_token(req, wall())
        if req.t_first_token is None:
            self._note_first_token(req, stamp())
        sched.activate(req)
        if req.done:                  # max_new_tokens == 1 degenerate case
            sched.finish(req, stamp())
        self._prefilling = None

    def _prefill_one(self, req: Request, wall) -> None:
        prompt = req.prefill_tokens
        bucket = self._bucket_for(len(prompt))
        tokens = np.zeros((1, bucket), np.int32)
        tokens[0, :len(prompt)] = prompt
        bt_row = np.full((self._bt_row_len(bucket),), TRASH_BLOCK,
                         np.int32)
        bt_row[:len(req.blocks)] = req.blocks
        t_p = time.perf_counter()
        first_tok, self._keys, self.pages = self._prefill_fn(bucket)(
            self.params, self.pages, self._keys, jnp.asarray(tokens),
            jnp.asarray([len(prompt) - 1], jnp.int32),
            jnp.asarray(bt_row), jnp.int32(req.slot))
        self._note_call(f"prefill_{bucket}", time.perf_counter() - t_p)
        if not req.generated:
            req.generated.append(int(np.asarray(first_tok)[0]))
            self._note_token(req, wall())
        # resumed after preemption: the prefill only rebuilt the prompt's
        # caches (KV pages / window ring / SSM state — bit-exact
        # recomputation); recorded tokens now replay through the decode
        # path (the backend that originally produced them), so generation
        # is token-exact regardless of pool pressure.

    # ------------------------------------------------------------- probe
    def _run_probe(self, iteration: int, tokens, bt, pos, active,
                   runnable: List[Request]) -> None:
        """Sampled selection-quality probe: re-run the current decode
        batch through a shadow step traced with the capture flag up
        (:mod:`repro.models.backends.probe`), so every sparse layer
        (socket / hard_lsh / quest) ships per-request recall /
        budget-utilization / forced-share stats to the host — then reduce over the active slots and emit
        one ``probe`` event per layer.  The shadow step is jitted
        WITHOUT donation (the production step still needs these pages)
        and its outputs are discarded; the production decode fn contains
        zero probe ops."""
        from repro.models.backends import probe as bprobe
        if self._probe_fn is None:
            serve = make_serve_step(self.cfg)

            def step(params, pages, keys, tokens, bt, pos, active):
                return self._decode_body(serve, params, pages, keys,
                                         tokens, bt, pos, active)

            self._probe_fn = jax.jit(step)
        t_p = time.perf_counter()
        bprobe.drain()                      # drop anything stale
        with bprobe.capture():
            self._probe_fn(self.params, self.pages, self._keys,
                           jnp.asarray(tokens), jnp.asarray(bt),
                           jnp.asarray(pos), jnp.asarray(active))
            jax.effects_barrier()           # flush the stat callbacks
        self._note_call("probe", time.perf_counter() - t_p)
        stats = bprobe.drain()
        rows = self.obs.probe.add(iteration, stats,
                                  [r.slot for r in runnable])
        for row in rows:
            self.obs.tracer.emit("probe", **row)
            if row["recall"] is not None:
                self.registry.histogram("probe_recall").record(
                    row["recall"])
                self.registry.histogram(
                    "probe_budget_utilization").record(
                        row["budget_utilization"])

    # ----------------------------------------------------------- metrics
    def _metrics(self, requests: List[Request],
                 wall: float) -> ServeMetrics:
        """End-of-run aggregate, derived entirely from the run's metrics
        registry.  The serving histograms retain exact samples
        (``exact=True``), so the percentiles below are byte-identical to
        ``np.percentile`` over the per-request series the engine used to
        aggregate directly (pinned by tests/test_observability.py)."""
        reg = self.registry
        total = int(reg.value("serve_tokens_total"))
        return ServeMetrics(
            num_requests=len(requests),
            total_generated=total,
            wall_s=wall,
            throughput_tok_s=total / wall if wall > 0 else float("nan"),
            ttft_s_mean=self._h_ttft.mean_exact(),
            ttft_s_p99=self._h_ttft.percentile_exact(99),
            token_latency_s_p50=self._h_stall.percentile_exact(50),
            token_latency_s_p99=self._h_stall.percentile_exact(99),
            preemptions=int(reg.value("serve_preemptions_total")),
            decode_iters=int(reg.value("serve_iters_total")),
            prefill_chunks=int(reg.value("serve_chunks_total")),
            intertoken_stall_s_max=self._h_stall.max_exact(),
            decode_iter_s_p99=self._h_iter.percentile_exact(99),
        )
