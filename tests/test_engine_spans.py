"""The engine's host spans, step-record host turnaround and named scopes:
a tiny engine served under the profiler writes every host span into the
profiler's trace in the order one iteration runs them, its v3 step
records carry the host turnaround, the profiler's clock anchor joins the two
traces, and the lowered decode step names every SOCKET phase."""

import gc
import glob
import os

import numpy as np
import pytest

from repro.serving import Request
from repro.serving.obs import events as ev
from repro.serving.obs.profiling import HOST_SPANS

STEP_SPANS = ("engine.schedule", "engine.tables", "decode", "engine.sync",
              "engine.emit")
SOCKET_SCOPES = ("socket.append", "socket.hash", "socket.score",
                 "socket.select", "socket.gather", "socket.attend")
MODEL_SCOPES = ("layer.proj", "layer.mlp", "model.head")


def _smoke_cfg(**socket):
    import dataclasses

    from repro.configs import get_config
    cfg = get_config("stablelm-12b").smoke().replace(
        attention_backend="socket")
    return cfg.replace(socket=dataclasses.replace(cfg.socket, **socket))


def _requests(cfg):
    rng = np.random.default_rng(11)
    return [Request(prompt=rng.integers(0, cfg.vocab_size, size=p).tolist(),
                    max_new_tokens=5, arrival=0.0) for p in (12, 20)]


def _host_events(pd, names):
    out = []
    for plane in pd.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            out.extend((e.name, float(e.start_ns), float(e.duration_ns))
                       for e in line.events if e.name in names)
    return sorted(out, key=lambda e: e[1])


def _profile_start_ns(pd):
    for plane in pd.planes:
        for name, value in plane.stats:
            if name == "profile_start_time":
                return int(value)
    raise AssertionError("trace has no profile_start_time")


@pytest.fixture(scope="module")
def profiled(tmp_path_factory):
    """One tiny run, profiled from its first iteration to its end, with a
    GC pass forced inside the window."""
    import jax
    from jax.profiler import ProfileData

    from repro.serving.engine import ContinuousBatchingEngine
    from repro.serving.obs import Observability

    cfg = _smoke_cfg()
    out = str(tmp_path_factory.mktemp("profile"))
    obs = Observability(profile_dir=out, profile_steps=1 << 20)
    engine = ContinuousBatchingEngine(cfg, rng=jax.random.PRNGKey(0),
                                      obs=obs)
    engine.warmup()

    def hook(eng, it):
        if it == 2:
            gc.collect()

    engine.iter_hook = hook
    reqs = _requests(cfg)
    m = engine.run(reqs, realtime=False)
    path = sorted(glob.glob(os.path.join(out, "plugins", "profile", "*",
                                         "*.xplane.pb")))[-1]
    steps = [e for e in obs.tracer.events if e["ev"] == "step"]
    return {"pd": ProfileData.from_file(path), "events": obs.tracer.events,
            "steps": steps, "metrics": m, "reqs": reqs}


def test_profiled_engine_writes_each_host_span_in_order_once_per_step(
        profiled):
    steps = profiled["steps"]
    kinds = [s["kind"] for s in steps]
    assert "mixed" in kinds and "decode" in kinds
    spans = _host_events(profiled["pd"], HOST_SPANS)
    per_step = [n for n, _, _ in spans if n not in ("host.gc", "engine.run")]
    want = [name if name != "decode" else kind
            for kind in kinds for name in STEP_SPANS]
    assert per_step == want
    # one engine.run span holds every other span: no host time in the
    # window goes unnamed between two phases
    (_, run_s, run_d), = [s for s in spans if s[0] == "engine.run"]
    assert all(run_s <= s and s + d <= run_s + run_d
               for n, s, d in spans if n != "engine.run")
    # the GC pass forced in the window is named, inside engine.emit
    gcs = [s for s in spans if s[0] == "host.gc"]
    emits = [s for s in spans if s[0] == "engine.emit"]
    assert gcs
    assert any(e[1] <= g[1] and g[1] + g[2] <= e[1] + e[2]
               for g in gcs for e in emits)
    # the window is closed: GC passes are no longer hooked
    assert not any(getattr(cb, "__self__", None) is not None
                   and cb.__name__ == "_on_gc" for cb in gc.callbacks)


def test_step_records_carry_the_v3_host_turnaround(profiled):
    steps = profiled["steps"]
    assert len(steps) == profiled["metrics"].decode_iters
    for e in profiled["events"]:
        ev.validate_event(e, version=3)
    assert steps[0]["host_s"] is None
    # host_s holds the step's own dispatch, its decode/mixed span
    anns = _host_events(profiled["pd"], ("decode", "mixed"))
    assert len(anns) == len(steps)
    for (_, _, dur_ns), a, b in zip(anns[1:], steps, steps[1:]):
        assert b["host_s"] * 1e9 >= dur_ns - 1e3, b
        assert b["ts"] > a["ts"]
    start = next(e for e in profiled["events"] if e["ev"] == "profile_start")
    assert isinstance(start["clock_ns"], int)


def test_step_dispatch_falls_on_its_annotation_through_clock_ns(profiled):
    pd = profiled["pd"]
    origin = _profile_start_ns(pd)
    start = next(e for e in profiled["events"] if e["ev"] == "profile_start")
    anns = _host_events(pd, ("decode", "mixed"))
    steps = profiled["steps"]
    assert [a[0] for a in anns] == [s["kind"] for s in steps]
    for (_, t_ns, _), s in zip(anns, steps):
        joined = s["ts"] * 1e9 + start["clock_ns"]
        assert abs(joined - (origin + t_ns)) < 1e6, (s, t_ns)


def test_older_traces_still_validate():
    """v2 step records (no host_s) and v2 profile_start events (no
    clock anchor) read as v2, and are refused as v3."""
    step = {"ev": "step", "ts": 0.5, "iter": 0, "kind": "decode",
            "occupancy": 2, "chunk_tokens": 0, "step_s": 0.01,
            "pool_free": 40, "pool_used": 7, "pool_high_water": 9,
            "waiting": 0, "prefilling": 0, "running": 2}
    prof = {"ev": "profile_start", "ts": 0.1, "dir": "/x", "steps": 4}
    for e in (step, prof):
        ev.validate_event(e, version=2)
        ev.validate_event(e, version=1)
        with pytest.raises(ValueError):
            ev.validate_event(e, version=3)
    lines = [ev.strict_dumps(x) for x in (
        {"ev": "trace_start", "ts": 0.0, "schema": 2}, prof, step)]
    assert [e["ev"] for e in ev.validate_jsonl(lines)] == [
        "trace_start", "profile_start", "step"]


def _lowered_text(cfg):
    import jax
    import jax.numpy as jnp

    from repro.serving.engine import ContinuousBatchingEngine
    engine = ContinuousBatchingEngine(cfg, rng=jax.random.PRNGKey(0))
    sv = engine.serving
    b = sv.max_batch
    return engine._decode_fn.lower(
        engine.params, engine.pages, engine._keys,
        jnp.zeros((b, 1), jnp.int32),
        jnp.zeros((b, sv.max_blocks_per_seq), jnp.int32),
        jnp.zeros((b,), jnp.int32), jnp.zeros((b,), bool)).as_text(
            debug_info=True)


@pytest.mark.parametrize("fused", [False, True])
def test_lowered_decode_step_names_every_scope(fused):
    text = _lowered_text(_smoke_cfg(use_paged_kernel=fused))
    if fused:
        want = ("socket.append", "socket.fused") + MODEL_SCOPES
    else:
        want = SOCKET_SCOPES + MODEL_SCOPES
    assert [s for s in want if s not in text] == []
    assert ("socket.fused" in text) == fused
