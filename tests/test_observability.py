"""Observability layer: event schema, metrics registry, tracer, Perfetto
exporter, scheduler/pool instrumentation, selection-quality probe — plus
the two engine-level contracts the layer must honor: tracing never
perturbs generation (token-bit-exact on vs off) and the disabled path
allocates zero tracing objects."""

import json
import math

import numpy as np
import pytest

from repro.serving import BlockPool, Request, Scheduler
from repro.serving.obs import events as ev
from repro.serving.obs.metrics import Histogram, Registry
from repro.serving.obs.perfetto import chrome_trace
from repro.serving.obs.tracing import Tracer

# ------------------------------------------------------------ strict JSON


def test_sanitize_replaces_nonfinite_floats():
    out = ev.sanitize({"a": float("nan"), "b": [1.5, float("inf")],
                       "c": {"d": -float("inf"), "e": "NaN"}})
    assert out == {"a": None, "b": [1.5, None], "c": {"d": None,
                                                      "e": "NaN"}}


def test_strict_dumps_never_emits_nan_tokens():
    s = ev.strict_dumps({"x": float("nan"), "y": 2.0})
    assert "NaN" not in s
    assert json.loads(s) == {"x": None, "y": 2.0}
    # round-trips through a compliant (strict) parser
    assert ev.strict_loads(s) == {"x": None, "y": 2.0}


def test_strict_loads_rejects_nan_tokens():
    for bad in ('{"x": NaN}', '{"x": Infinity}', '{"x": -Infinity}'):
        with pytest.raises(ValueError):
            ev.strict_loads(bad)


# ----------------------------------------------------------- event schema


def _step_event(**over):
    base = {"ev": "step", "ts": 0.5, "iter": 0, "kind": "decode",
            "occupancy": 2, "chunk_tokens": 0, "step_s": 0.01,
            "pool_free": 40, "pool_used": 7, "pool_high_water": 9,
            "waiting": 0, "prefilling": 0, "running": 2, "host_s": 0.004}
    base.update(over)
    return base


def test_validate_event_accepts_conforming_events():
    ev.validate_event(_step_event())
    ev.validate_event({"ev": "trace_start", "ts": 0.0,
                       "schema": ev.SCHEMA_VERSION})      # optionals absent
    ev.validate_event({"ev": "admit", "ts": 0.1, "rid": 3, "slot": 0,
                       "blocks": 2, "resume": False, "wait_s": 0.2})
    # a sanitized non-finite float field is None and still a valid float
    ev.validate_event({"ev": "first_token", "ts": 0.1, "rid": 3,
                       "ttft_s": None})


def test_validate_event_is_strict_both_ways():
    with pytest.raises(ValueError):                       # unknown type
        ev.validate_event({"ev": "nope", "ts": 0.0})
    with pytest.raises(ValueError):                       # missing ts
        ev.validate_event({"ev": "step"})
    with pytest.raises(ValueError):                       # None where str
        ev.validate_event(_step_event(kind=None))
    missing = _step_event()
    del missing["pool_high_water"]
    with pytest.raises(ValueError):
        ev.validate_event(missing)
    with pytest.raises(ValueError):                       # wrong type
        ev.validate_event(_step_event(iter="0"))
    with pytest.raises(ValueError):                       # bool is not int
        ev.validate_event(_step_event(iter=True))
    with pytest.raises(ValueError):                       # unknown field
        ev.validate_event(_step_event(extra=1))


def test_validate_jsonl_requires_version_handshake():
    start = ev.strict_dumps({"ev": "trace_start", "ts": 0.0,
                             "schema": ev.SCHEMA_VERSION})
    step = ev.strict_dumps(_step_event())
    events = ev.validate_jsonl([start, "", step])         # blank lines ok
    assert [e["ev"] for e in events] == ["trace_start", "step"]
    with pytest.raises(ValueError):                       # no handshake
        ev.validate_jsonl([step])
    with pytest.raises(ValueError):                       # empty trace
        ev.validate_jsonl([])
    future = ev.strict_dumps({"ev": "trace_start", "ts": 0.0,
                              "schema": ev.SCHEMA_VERSION + 1})
    with pytest.raises(ValueError):                       # unknown version
        ev.validate_jsonl([future])


def test_tracer_validates_at_emit_time_and_streams_jsonl(tmp_path):
    path = tmp_path / "sub" / "trace.jsonl"               # dir auto-created
    with Tracer(str(path)) as tr:
        tr.ensure_start()
        tr.ensure_start()                                 # idempotent
        run = tr.begin_run(requests=2)
        with pytest.raises(ValueError):                   # rejected AND
            tr.emit("step", iter=0)                       # not recorded
        tr.end_run(run, requests=2, generated=7, wall_s=float("nan"))
    events = ev.validate_jsonl(path.read_text().splitlines())
    assert [e["ev"] for e in events] == ["trace_start", "run_start",
                                         "run_end"]
    assert events == [e for e in events if e is not None]
    assert events[-1]["wall_s"] is None                   # sanitized
    assert events == ev.sanitize(events)                  # in-memory copy
    assert [e["ev"] for e in Tracer(None).events] == []   # memory-only ok


# -------------------------------------------------------------- histogram


def test_histogram_streaming_percentile_error_bound():
    rng = np.random.default_rng(0)
    samples = rng.lognormal(mean=-3.0, sigma=1.2, size=4000)
    h = Histogram(growth=1.05)
    for v in samples:
        h.record(v)
    assert h.count == len(samples)
    assert h.total == pytest.approx(samples.sum())
    assert h.vmin == samples.min() and h.vmax == samples.max()
    for q in (10, 50, 90, 99):
        exact = np.percentile(samples, q)
        est = h.percentile(q)
        # log-bucket midpoint answer: relative error <= growth - 1
        assert abs(est - exact) / exact <= h.growth - 1.0, (q, est, exact)


def test_histogram_exact_views_match_numpy():
    rng = np.random.default_rng(1)
    samples = rng.exponential(0.01, size=257)
    h = Histogram(exact=True)
    for v in samples:
        h.record(float(v))
    for q in (0, 50, 99, 100):
        assert h.percentile_exact(q) == float(np.percentile(samples, q))
    assert h.mean_exact() == float(np.mean(samples))
    assert h.max_exact() == max(float(v) for v in samples)
    with pytest.raises(AssertionError):                   # not retained
        Histogram().percentile_exact(50)


def test_histogram_empty_and_underflow():
    h = Histogram()
    assert math.isnan(h.percentile(50))
    assert h.to_json() == {"count": 0, "sum": 0.0, "min": None,
                           "max": None, "p50": None, "p99": None}
    h.record(0.0)                                         # underflow bucket
    h.record(-1.0)
    h.record(4.0)
    assert h.underflow == 2 and h.count == 3
    assert h.percentile(50) == -1.0                       # min(vmin, 0)
    assert h.percentile(100) == 4.0                       # clamped to vmax
    # strict-JSON-safe snapshot even with negative values recorded
    json.dumps(ev.sanitize(h.to_json()), allow_nan=False)


# --------------------------------------------------------------- registry


def test_registry_families_labels_and_value():
    reg = Registry()
    reg.counter("preempt", cause="lru").inc()
    reg.counter("preempt", cause="lru").inc(2)            # same instrument
    reg.counter("preempt", cause="stall").inc()
    assert reg.counter("preempt", cause="lru").value == 3
    assert reg.value("preempt") == 4                      # sums over labels
    assert reg.value("absent") == 0
    assert reg.get("preempt", cause="lru").value == 3
    assert reg.get("preempt", cause="nope") is None
    reg.gauge("free").set(17)
    assert reg.value("free") == 17
    with pytest.raises(ValueError):                       # kind clash
        reg.gauge("preempt", cause="oom")
    with pytest.raises(ValueError):                       # negative inc
        reg.counter("preempt", cause="lru").inc(-1)


def test_registry_prometheus_text_format():
    reg = Registry()
    reg.counter("serve_tokens_total").inc(5)
    reg.gauge("pool_blocks_free", pool="kv").set(3)
    h = reg.histogram("iter_s")
    for v in (0.001, 0.002, 0.004, 0.008):
        h.record(v)
    text = reg.prometheus_text()
    lines = text.splitlines()
    assert "# TYPE serve_tokens_total counter" in lines
    assert "serve_tokens_total 5" in lines
    assert '# TYPE pool_blocks_free gauge' in lines
    assert 'pool_blocks_free{pool="kv"} 3' in lines
    assert "# TYPE iter_s histogram" in lines
    assert 'iter_s_bucket{le="+Inf"} 4' in lines
    assert "iter_s_count 4" in lines
    assert any(line.startswith("iter_s_sum ") for line in lines)
    # cumulative bucket counts are monotone and end at count
    cums = [int(line.rsplit(" ", 1)[1]) for line in lines
            if line.startswith("iter_s_bucket")]
    assert cums == sorted(cums) and cums[-1] == 4


def test_registry_snapshot_is_strict_json():
    reg = Registry()
    reg.histogram("empty_series")                         # percentiles NaN
    reg.counter("n", kind="a").inc()
    snap = reg.snapshot()
    json.dumps(snap, allow_nan=False)                     # no NaN anywhere
    assert snap["empty_series"]["values"]["_"]["p99"] is None
    assert snap["n"]["values"]['{kind="a"}'] == 1


# --------------------------------------------- pool + scheduler telemetry


def test_block_pool_tracks_high_water():
    pool = BlockPool(num_blocks=8)
    assert pool.stats() == {"free": 7, "used": 0, "high_water": 0,
                            "shared": 0}
    a = pool.alloc(3)
    b = pool.alloc(2)
    pool.free(b)
    assert pool.stats() == {"free": 4, "used": 3, "high_water": 5,
                            "shared": 0}
    pool.free(a)
    assert pool.stats()["high_water"] == 5                # sticky
    assert pool.alloc(99) is None
    assert pool.stats()["high_water"] == 5                # failed alloc: no


def _obs_sched(num_blocks, *, max_batch=2, prefill_chunk=0):
    sched = Scheduler(BlockPool(num_blocks), max_batch=max_batch,
                      max_blocks_per_seq=8, block_size=8,
                      prefill_chunk=prefill_chunk)
    reg, tracer = Registry(), Tracer(None)
    tracer.ensure_start()
    sched.bind_obs(reg, tracer)
    return sched, reg, tracer


def _evs(tracer, kind):
    return [e for e in tracer.events if e["ev"] == kind]


def test_scheduler_emits_admission_wait_and_lifecycle_events():
    sched, reg, tracer = _obs_sched(16)
    sched.submit(Request(prompt=[1] * 8, max_new_tokens=4, arrival=0.5))
    req = sched.try_admit(now=2.5)                        # realtime clock
    assert req is not None
    (admit,) = _evs(tracer, "admit")
    assert admit["rid"] == req.rid and admit["resume"] is False
    assert admit["wait_s"] == pytest.approx(2.0)
    assert reg.histogram("admission_wait_s").count == 1
    assert reg.histogram("admission_wait_s").total == pytest.approx(2.0)
    sched.activate(req)
    sched.finish(req, now=3.0)
    assert reg.value("serve_requests_total") == 1
    (fin,) = _evs(tracer, "finish")
    assert fin["rid"] == req.rid and fin["preemptions"] == 0
    # offline clocks (now=inf) record no wait — it is unmeasurable
    sched.submit(Request(prompt=[1] * 8, max_new_tokens=4, arrival=0.0))
    req2 = sched.try_admit(now=float("inf"))
    assert req2 is not None
    assert reg.histogram("admission_wait_s").count == 1   # unchanged
    assert "wait_s" not in _evs(tracer, "admit")[-1]


def test_scheduler_counts_preemptions_by_cause():
    sched, reg, tracer = _obs_sched(16)
    sched.submit(Request(prompt=[1] * 8, max_new_tokens=4, arrival=0.0))
    req = sched.try_admit(now=0.0)
    sched.activate(req)
    sched.preempt(req)                                    # default cause
    assert reg.counter("serve_preemptions_total", cause="manual").value \
        == 1
    assert reg.value("serve_preemptions_total") == 1
    (pre,) = _evs(tracer, "preempt")
    assert pre["cause"] == "manual" and pre["state"] == "decode"
    assert pre["blocks_freed"] == 1
    assert _evs(tracer, "admit")[-1]["resume"] is False
    req2 = sched.try_admit(now=0.0)                       # resumes
    assert req2 is req
    assert _evs(tracer, "admit")[-1]["resume"] is True


def test_scheduler_counts_withheld_chunk_grants():
    # A decodes holding 1 block; B is mid-prefill needing a 2nd block for
    # its next chunk while the pool is (artificially) drained -> the grant
    # is withheld (counter + event), then proceeds once blocks free up.
    sched, reg, tracer = _obs_sched(5, prefill_chunk=8)
    a = Request(prompt=[1] * 8, max_new_tokens=1, arrival=0.0)
    b = Request(prompt=[2] * 16, max_new_tokens=8, arrival=0.0)
    sched.submit(a)
    sched.submit(b)
    sched.activate(sched.try_admit(now=0.0))              # a decodes
    assert sched.try_admit(now=0.0) is b                  # first chunk fits
    first = sched.grant_chunk(b)
    assert first is not None and not first.final
    sched.advance_chunk(b, first)
    hold = sched.pool.alloc(sched.pool.num_free)          # drain the pool
    assert sched.grant_chunk(b) is None                   # withheld
    assert b.state == "prefill"                           # NOT preempted
    assert reg.value("serve_chunks_withheld_total") == 1
    (wh,) = _evs(tracer, "chunk_withheld")
    assert wh["rid"] == b.rid and wh["free_blocks"] == 0
    sched.pool.free(hold)
    chunk = sched.grant_chunk(b)                          # now proceeds
    assert chunk is not None and chunk.final
    grants = _evs(tracer, "chunk_grant")
    assert [g["start"] for g in grants] == [0, 8]
    assert reg.value("serve_preemptions_total") == 0


# --------------------------------------------------------------- perfetto


def test_chrome_trace_spans_and_counters():
    tr = Tracer(None)
    tr.ensure_start()
    run = tr.begin_run(requests=1)
    tr.emit("submit", rid=0, prompt_tokens=16, max_new_tokens=4,
            arrival=0.0)
    tr.emit("admit", rid=0, slot=0, blocks=2, resume=False)
    tr.emit("compile", fn="mixed", seconds=0.25)
    tr.emit("first_token", rid=0, ttft_s=0.1)
    tr.emit("step", **{k: v for k, v in _step_event().items()
                       if k not in ("ev", "ts")})
    tr.emit("probe", iter=0, layer=1, requests=1, static_k=16,
            recall=0.75, budget_utilization=0.5, forced_share=0.9,
            selected_mean=8.0, budget_mean=16.0)
    tr.emit("finish", rid=0, generated=4, preemptions=0)
    tr.end_run(run, requests=1, generated=4, wall_s=0.5)
    trace = chrome_trace(tr.events)
    out = trace["traceEvents"]
    spans = {e["name"] for e in out if e["ph"] == "X"}
    assert {"queued", "prefill", "decode", "compile mixed"} <= spans
    counters = {e["name"] for e in out if e["ph"] == "C"}
    assert {"pool_blocks", "batch", "probe_recall_l1"} <= counters
    # phases partition the request's lifetime: queued ends where prefill
    # starts, prefill where decode starts
    req_spans = {e["name"]: e for e in out
                 if e["ph"] == "X" and e["pid"] == 1}
    assert req_spans["queued"]["ts"] + req_spans["queued"]["dur"] == \
        pytest.approx(req_spans["prefill"]["ts"])
    assert req_spans["prefill"]["ts"] + req_spans["prefill"]["dur"] == \
        pytest.approx(req_spans["decode"]["ts"])
    json.dumps(trace, allow_nan=False)                    # strict export


# ---------------------------------------------------------- engine-level
#
# One module-scoped workload served twice — traced+probed vs bare — feeds
# the parity, schema, metrics-equivalence and probe tests below without
# recompiling per test.


def _smoke_cfg():
    from repro.configs import get_config
    return get_config("stablelm-12b").smoke().replace(
        attention_backend="socket")


_PLENS = (8, 20, 24)
_MAX_NEW = 6


def _requests(cfg):
    rng = np.random.default_rng(7)
    return [Request(prompt=rng.integers(0, cfg.vocab_size, size=p).tolist(),
                    max_new_tokens=_MAX_NEW, arrival=0.0) for p in _PLENS]


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    import jax
    from repro.serving.engine import ContinuousBatchingEngine
    from repro.serving.obs import Observability

    cfg = _smoke_cfg()
    path = tmp_path_factory.mktemp("obs") / "trace.jsonl"
    obs = Observability(str(path), probe_every=2)
    traced_engine = ContinuousBatchingEngine(cfg, rng=jax.random.PRNGKey(0),
                                             obs=obs)
    traced_reqs = _requests(cfg)
    traced_metrics = traced_engine.run(traced_reqs, realtime=False)
    obs.close()

    bare_engine = ContinuousBatchingEngine(cfg, rng=jax.random.PRNGKey(0))
    bare_reqs = _requests(cfg)
    bare_metrics = bare_engine.run(bare_reqs, realtime=False)
    return {"path": path, "obs": obs,
            "traced": (traced_engine, traced_reqs, traced_metrics),
            "bare": (bare_engine, bare_reqs, bare_metrics)}


def test_engine_trace_file_is_schema_valid(served):
    _, reqs, m = served["traced"]
    with open(served["path"]) as f:
        events = ev.validate_jsonl(f)
    assert events == served["obs"].tracer.events           # file == memory
    head = events[0]
    assert head["ev"] == "trace_start" and head["backend"] == "socket"
    assert head["arch"] == "stablelm-12b" and head["layers_paged"] > 0
    by_kind = {}
    for e in events:
        by_kind.setdefault(e["ev"], []).append(e)
    # every request has a full lifecycle
    for kind in ("submit", "admit", "first_token", "finish"):
        assert sorted(e["rid"] for e in by_kind[kind]) == \
            sorted(r.rid for r in reqs), kind
    # one step record per engine iteration, numbered densely
    assert [e["iter"] for e in by_kind["step"]] == \
        list(range(m.decode_iters))
    assert sum(e["kind"] == "mixed" for e in by_kind["step"]) == \
        m.prefill_chunks
    # chunk grants cover each prompt exactly once, in cursor order
    for r in reqs:
        grants = [e for e in by_kind["chunk_grant"] if e["rid"] == r.rid]
        assert sum(g["tokens"] for g in grants) == len(r.prompt)
        assert grants[-1]["final"] is True
    # unwarmed run: the first mixed/decode/probe dispatches are compiles
    assert {"mixed", "probe"} <= {e["fn"] for e in by_kind["compile"]}
    assert by_kind["run_end"][0]["generated"] == m.total_generated
    assert max(e["pool_high_water"] for e in by_kind["step"]) > 0


def test_tracing_is_token_bit_exact_vs_disabled(served):
    _, traced_reqs, tm = served["traced"]
    _, bare_reqs, bm = served["bare"]
    for t, b in zip(traced_reqs, bare_reqs):
        assert t.generated == b.generated
    assert (tm.total_generated, tm.decode_iters, tm.prefill_chunks) == \
        (bm.total_generated, bm.decode_iters, bm.prefill_chunks)


def test_disabled_path_constructs_no_tracing_objects(monkeypatch):
    """obs=None must never touch Tracer/SelectionProbe/Profiler — the
    hot loop's disabled path allocates zero tracing objects."""
    import jax
    from repro.serving.engine import ContinuousBatchingEngine
    from repro.serving.obs import probe as obs_probe
    from repro.serving.obs import profiling, tracing

    def boom(self, *a, **kw):
        raise AssertionError("tracing object constructed with obs=None")

    monkeypatch.setattr(tracing.Tracer, "__init__", boom)
    monkeypatch.setattr(obs_probe.SelectionProbe, "__init__", boom)
    monkeypatch.setattr(profiling.Profiler, "__init__", boom)
    cfg = _smoke_cfg()
    engine = ContinuousBatchingEngine(cfg, rng=jax.random.PRNGKey(0))
    reqs = _requests(cfg)[:1]
    engine.run(reqs, realtime=False)
    assert reqs[0].state == "finished"
    assert len(reqs[0].generated) == _MAX_NEW


def test_serve_metrics_are_byte_identical_to_direct_aggregation(served):
    """ServeMetrics now derives from the registry's exact histograms; it
    must equal the direct aggregation over the per-request series,
    float-for-float.  Token latency is the gap between consecutive
    tokens of one request."""
    engine, reqs, m = served["traced"]
    ttfts = [r.t_first_token - r.arrival for r in reqs]
    stalls = [b - a for r in reqs
              for a, b in zip(r.token_walls, r.token_walls[1:])]
    assert m.num_requests == len(reqs)
    assert m.total_generated == sum(len(r.generated) for r in reqs)
    assert m.ttft_s_mean == float(np.mean(ttfts))
    assert m.ttft_s_p99 == float(np.percentile(ttfts, 99))
    assert m.token_latency_s_p50 == float(np.percentile(stalls, 50))
    assert m.token_latency_s_p99 == float(np.percentile(stalls, 99))
    assert engine.registry.get("serve_token_latency_s") is None
    assert m.intertoken_stall_s_max == max(stalls)
    assert m.preemptions == sum(r.preemptions for r in reqs)
    reg = engine.registry
    assert reg.value("serve_tokens_total") == m.total_generated
    assert reg.value("serve_iters_total") == m.decode_iters
    assert reg.counter("serve_iters_total", kind="mixed").value == \
        m.prefill_chunks == reg.value("serve_chunks_total")
    iters = reg.histogram("serve_iter_s", exact=True)
    assert m.decode_iter_s_p99 == \
        float(np.percentile(iters.samples, 99))
    # end-of-run gauges: everything was returned to the pool
    assert reg.get("pool_blocks_used").value == 0
    assert reg.get("pool_blocks_high_water").value == \
        engine.pool.high_water > 0
    json.dumps(m.to_json(), allow_nan=False)
    assert reg.prometheus_text().startswith("# TYPE")


def test_serve_metrics_to_json_nulls_nonfinite():
    from repro.serving.engine import ServeMetrics

    m = ServeMetrics(
        num_requests=0, total_generated=0, wall_s=0.0,
        throughput_tok_s=float("nan"), ttft_s_mean=float("nan"),
        ttft_s_p99=float("nan"), token_latency_s_p50=float("nan"),
        token_latency_s_p99=float("inf"), preemptions=0, decode_iters=0,
        prefill_chunks=0, intertoken_stall_s_max=float("nan"),
        decode_iter_s_p99=float("nan"))
    out = m.to_json()
    assert out["throughput_tok_s"] is None
    assert out["token_latency_s_p99"] is None
    assert out["num_requests"] == 0 and out["wall_s"] == 0.0
    json.dumps(out, allow_nan=False)


def test_engine_probe_rows_sample_every_layer(served):
    engine, reqs, m = served["traced"]
    probe = served["obs"].probe
    assert probe.rows, "probe never fired"
    layers = {r["layer"] for r in probe.rows}
    n_layers = len(engine.cfg.layer_specs)
    assert layers == set(range(n_layers))                 # all socket layers
    iters = sorted({r["iter"] for r in probe.rows})
    assert all(i % probe.every == 0 for i in iters)
    for row in probe.rows:
        assert 0.0 <= row["recall"] <= 1.0
        assert 0.0 < row["budget_utilization"] <= 1.0
        assert 0.0 <= row["forced_share"] <= 1.0
        assert 0 < row["selected_mean"] <= row["budget_mean"] \
            <= row["static_k"]
    # probe events mirror the rows; registry streams recall
    probe_events = [e for e in served["obs"].tracer.events
                    if e["ev"] == "probe"]
    assert len(probe_events) == len(probe.rows)
    reg = engine.registry
    assert reg.histogram("probe_recall").count == len(probe.rows)
    summary = served["obs"].probe_summary()
    assert summary["rows"] == len(probe.rows)
    assert summary["probe_steps"] == len(iters)
    assert summary["recall"] == pytest.approx(
        np.mean([r["recall"] for r in probe.rows]), abs=1e-6)


def test_probe_recall_is_one_when_budget_covers_context():
    """With sparsity=1 the SOCKET budget equals the context length, so
    the selection must contain every valid position — the probe's recall
    against dense top-k is exactly 1 and the budget fully used.  Pins the
    probe's reference math against a case with a known answer."""
    import dataclasses

    import jax
    from repro.serving.engine import ContinuousBatchingEngine
    from repro.serving.obs import Observability

    cfg = _smoke_cfg()
    cfg = cfg.replace(socket=dataclasses.replace(
        cfg.socket, sparsity=1.0, min_k=8))
    obs = Observability(probe_every=1)
    engine = ContinuousBatchingEngine(cfg, rng=jax.random.PRNGKey(0),
                                      obs=obs)
    reqs = _requests(cfg)[:2]
    engine.run(reqs, realtime=False)
    assert obs.probe.rows
    for row in obs.probe.rows:
        assert row["recall"] == 1.0, row
        # budget == context length == realized selection, exactly
        assert row["selected_mean"] == row["budget_mean"], row
        assert row["budget_utilization"] == pytest.approx(
            row["selected_mean"] / row["static_k"], abs=1e-6), row


@pytest.mark.parametrize("backend", ["socket", "hard_lsh", "quest"])
def test_probe_selection_quality_parity_quantized(backend):
    """int8 pool pages must not change what the model *selects* or
    *emits*: socket/hard_lsh score against full-precision bits/vnorms,
    so the greedy generations and every selection-side probe statistic
    (budget_utilization / forced_share / selected_mean / budget_mean)
    are bit-identical to the bf16-pages run; quest recomputes its page
    bounds from the quantized round-trip, so its recall is only
    *bounded* against bf16.  Recall is never asserted exactly equal:
    the probe's dense reference recomputes attention mass from the
    cached (dequantized) K rows, so the reference moves with the
    storage dtype even when the selection does not.  (fp8's 3-bit
    mantissa perturbs attention outputs enough for greedy argmax to
    flip mid-trajectory, so trajectory-level parity is an int8-only
    contract; fp8 selection identity is pinned per-step by the
    kernel-harness BITWISE checks and at serving level by the bench
    quantized rows.)"""
    import jax
    from repro.serving.engine import ContinuousBatchingEngine
    from repro.serving.obs import Observability

    runs = {}
    for kvd in ("bf16", "int8"):
        cfg = _smoke_cfg().replace(attention_backend=backend)
        cfg = cfg.replace(serving=cfg.serving.replace(kv_dtype=kvd))
        obs = Observability(probe_every=2)
        engine = ContinuousBatchingEngine(cfg, rng=jax.random.PRNGKey(0),
                                          obs=obs)
        reqs = _requests(cfg)
        engine.run(reqs, realtime=False)
        assert obs.probe.rows, kvd
        runs[kvd] = {"summary": obs.probe_summary(),
                     "gens": [r.generated for r in reqs]}

    base, quant = runs["bf16"], runs["int8"]
    assert quant["gens"] == base["gens"]
    if backend in ("socket", "hard_lsh"):
        for stat in ("budget_utilization", "forced_share",
                     "selected_mean", "budget_mean"):
            assert quant["summary"][stat] == base["summary"][stat], stat
        tol = 2e-3
    else:
        tol = 2e-2
    assert abs(quant["summary"]["recall"]
               - base["summary"]["recall"]) <= tol
