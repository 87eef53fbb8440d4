"""Device time by named scope and idle gaps named by host spans
(``scopes.py``), on synthetic ops and on the recorded CPU trace; the
form that makes a path segment a scope; the metric readers that read
scope time from a traced run's context; and the host turnaround metric
on synthetic step records."""

import re
import sys
from pathlib import Path
from types import SimpleNamespace as NS

REPO = Path(__file__).resolve().parents[3]
sys.path[:0] = [str(REPO), str(REPO / "src")]

import pytest  # noqa: E402

from benchmarks.chip import harness, scopes  # noqa: E402
from benchmarks.chip import trace as tr  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"


def _plane(name, lines):
    return NS(name=name, lines=[
        NS(name=ln, events=[NS(name=n, start_ns=s, duration_ns=e - s)
                            for n, s, e in evs])
        for ln, evs in lines.items()])


def test_scope_attribution_credits_the_innermost_scope_with_self_time():
    # a program of 100 ns holding a loop whose body ops are nested in it
    ops = [("while", 0, 80), ("score", 0, 30), ("hash", 30, 40),
           ("topk", 40, 60), ("copy", 60, 75), ("head", 85, 100)]
    paths = {"while": "jit(step)/while:",
             "score": "jit(step)/while/body/socket.score/mul:",
             "hash": "jit(step)/while/body/socket.score/socket.hash/div:",
             "topk": "jit(step)/while/body/socket.select/sort:sort",
             "head": "jit(step)/model.head/dot_general:"}
    pd = NS(planes=[
        _plane("/device:TPU:0", {"XLA Ops": ops,
                                 "XLA Modules": [("jit_step", 0, 100)]}),
        _plane("/host:CPU", {"python": [("decode", -10, 1),
                                        ("engine.sync", 1, 100),
                                        ("decode", 100, 200)]})])
    out = scopes.breakdown(pd, {"/device:TPU:0": paths},
                           label_names=("engine.sync",))
    got = {k: v * 1e9 for k, v in out["scope_device_s"].items()}
    assert got == pytest.approx({"socket.score": 30, "socket.hash": 10,
                                 "socket.select": 20, "model.head": 15,
                                 "unscoped": 20})
    # scopes plus unscoped are the ops' self time: the device's busy time
    assert sum(got.values()) == pytest.approx(out["busy_s"] * 1e9)
    assert out["programs_in_window"] == 1.0
    assert out["scope_ms_per_step"]["socket.score"] == pytest.approx(30e-6)
    # the gaps (-10..0, 80..85, 100..200) by the host span they fell in
    assert out["idle_by_span"] == pytest.approx(
        {"decode": 10e-9 + 100e-9, "engine.sync": 5e-9})
    assert scopes.scope_of("jit(step)/while/body/layer.mlp/socket.select/"
                           "x:") == "socket.select"
    assert scopes.scope_of("jit(step)/while/body/add:add") == "unscoped"


def test_a_program_cut_by_the_window_counts_by_its_share_inside():
    progs = [("jit_step", 0, 100), ("jit_step", 150, 250),
             ("jit_step", 300, 400)]
    assert scopes.programs_in_window(progs, 0, 200) == 1.5
    assert scopes.programs_in_window(progs, -50, 450) == 3.0


def _pb(*fields):
    """A protobuf message from (field, value) pairs: int values as
    varints, bytes and str as length-delimited fields."""
    def varint(x):
        out = b""
        while True:
            out += bytes([(x & 0x7F) | (0x80 if x > 0x7F else 0)])
            x >>= 7
            if not x:
                return out
    out = b""
    for f, v in fields:
        if isinstance(v, int):
            out += varint(f << 3) + varint(v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += varint((f << 3) | 2) + varint(len(v)) + v
    return out


def test_op_paths_reads_each_ops_metadata_stat():
    stat_meta = [(5, _pb((1, i), (2, _pb((1, i), (2, n)))))
                 for i, n in ((7, "tf_op"), (8, "flops"),
                              (9, "jit(step)/socket.gather/gather:"))]
    events = [
        (4, _pb((1, 1), (2, _pb((1, 1), (2, "%fusion.1 = f32[8] fusion()"),
                                (5, _pb((1, 8), (4, 99))),
                                (5, _pb((1, 7), (5, "jit(step)/socket."
                                                    "score/sub:"))))))),
        # the string by reference to a stat metadata's name
        (4, _pb((1, 2), (2, _pb((1, 2), (2, "%gather.3 = bf16[4] gather()"),
                                (5, _pb((1, 7), (7, 9))))))),
        (4, _pb((1, 3), (2, _pb((1, 3), (2, "%copy.4 = u32[2] copy()"),
                                (5, _pb((1, 8), (4, 1))))))),
    ]
    plane = _pb((1, 5), (2, "/device:TPU:0"), *stat_meta, *events,
                (3, _pb((1, 1), (2, "XLA Ops"))))
    raw = _pb((1, plane), (1, _pb((2, "/host:CPU"))))
    assert scopes.op_paths(raw) == {"/device:TPU:0": {
        "%fusion.1 = f32[8] fusion()": "jit(step)/socket.score/sub:",
        "%gather.3 = bf16[4] gather()": "jit(step)/socket.gather/gather:"}}


def test_host_spans_name_the_gaps_and_leave_the_reduction_as_it_was():
    pd, paths = scopes.load(str(DATA / "cpu.xplane.pb"))
    kw = dict(device_prefix="/host:CPU", ops_line="tf_XLAPjRtCpuClient",
              modules_line="tf_XLAPjRtCpuClient")
    red = tr.reduce(pd, **kw)
    out = scopes.breakdown(pd, paths, label_names=("bench.hook",), **kw)
    for key in ("window_s", "busy_s", "idle_share", "top_ops",
                "step_device_s", "steps"):
        assert out[key] == red[key], key
    assert sum(out["idle_by_span"].values()) == pytest.approx(
        sum(red["idle_by_span"].values()))
    # two of the three 5 ms sleeps fall in the window the steps set, and
    # the hook span names them
    assert "bench.hook" not in red["idle_by_span"]
    assert out["idle_by_span"]["bench.hook"] >= 0.010
    assert out["span_ms"]["decode"]["n"] == 3
    # a CPU client's ops carry no op-name path: everything is unscoped
    assert set(out["scope_device_s"]) == {"unscoped"}


def _ctx(kinds=("decode",) * 3, host=(None, 0.002, 0.004)):
    return {"step_events": [{"kind": k, "occupancy": 4, "host_s": h}
                            for k, h in zip(kinds, host)]}


def test_host_ms_reads_the_decode_step_records():
    read = harness.metric_reader("host_ms.decode")
    assert read(_ctx()) == pytest.approx(3.0)
    # a mixed step in the window: no reading
    assert read(_ctx(kinds=("decode", "mixed", "decode"))) is None
    # step records from before the phase times (or none at all)
    old = _ctx()
    for e in old["step_events"]:
        del e["host_s"]
    assert read(old) is None
    assert read({"step_events": []}) is None


TODAYS_SCOPES = {"socket.append", "socket.hash", "socket.score",
                 "socket.select", "socket.gather", "socket.attend",
                 "socket.fused", "layer.proj", "layer.mlp", "model.head"}


def test_every_named_scope_of_the_program_has_the_scope_form():
    named = set()
    for path in (REPO / "src" / "repro").rglob("*.py"):
        named |= set(re.findall(r"named_scope\(\s*[\"']([^\"']*)[\"']",
                                path.read_text()))
    assert TODAYS_SCOPES <= named
    assert all(scopes.SCOPE.fullmatch(n) for n in named), named
    for name in named:
        assert scopes.scope_of(f"jit(step)/while/body/{name}/mul:") == name


def test_no_op_name_of_a_recorded_trace_has_the_scope_form():
    pd, _ = tr.load(str(DATA / "cpu.xplane.pb"))
    ops = {e.name for p in pd.planes for ln in p.lines
           if ln.name != "python" for e in ln.events}
    assert "dot_general.1" in ops
    ops |= {"%fusion.221", "copy-start.1", "jit(_decode_step)/jit(main)/"
            "while/body/closed_call/pallas_call:", "transpose(jvp(x.y))"}
    for op in ops:
        assert scopes.scope_of(op) == "unscoped", op


def _traced(paths, kinds=("decode",)):
    """A one-program trace of ops under ``paths`` ({op: path}), 10 ns
    each, with the host step spans of ``kinds``."""
    ops = [(op, 10 * i, 10 * i + 10) for i, op in enumerate(paths)]
    end = 10 * len(ops)
    steps = [(k, -5 + i, end + i) for i, k in enumerate(kinds)]
    return NS(planes=[
        _plane("/device:TPU:0", {"XLA Ops": ops,
                                 "XLA Modules": [("jit_step", 0, end)]}),
        _plane("/host:CPU", {"python": steps})]), {"/device:TPU:0": paths}


def test_a_scope_the_program_adds_later_is_attributed():
    pd, paths = _traced({"a": "jit(step)/while/body/layer.moe/dot_general:",
                         "b": "jit(step)/while/body/layer.mamba/ssd.chunk/"
                              "mul:",
                         "c": "jit(step)/socket.score/add:"})
    got = scopes.breakdown(pd, paths)["scope_device_s"]
    assert got == pytest.approx({"layer.moe": 1e-8, "ssd.chunk": 1e-8,
                                 "socket.score": 1e-8})


SOCKET_METRICS = {"socket_score_ms.decode": ("socket.score", "socket.hash"),
                  "socket_select_ms.decode": ("socket.select",),
                  "socket_attend_ms.decode": ("socket.gather",
                                              "socket.attend")}


def test_a_metric_reader_reads_scope_time_of_a_recorded_trace():
    red = harness.read_trace(str(DATA / "cpu.xplane.pb"),
                             device_prefix="/host:CPU",
                             ops_line="tf_XLAPjRtCpuClient",
                             modules_line="tf_XLAPjRtCpuClient")
    plain, _ = tr.load(str(DATA / "cpu.xplane.pb"))
    plain = tr.reduce(plain, device_prefix="/host:CPU",
                      ops_line="tf_XLAPjRtCpuClient",
                      modules_line="tf_XLAPjRtCpuClient")
    # the window is still the step spans': the gaps' names alone differ
    for key in ("window_s", "busy_s", "idle_share", "step_device_s"):
        assert red[key] == plain[key], key
    ctx = {"trace": red, "step_events": [{"kind": "decode"}] * 3}
    n = red["programs_in_window"]
    assert n > 0
    read = harness.metric_reader("unscoped_ms.decode", root=DATA)
    assert read(ctx) == pytest.approx(
        1e3 * red["scope_device_s"]["unscoped"] / n)
    assert read(ctx) == pytest.approx(1e3 * red["busy_s"] / n)
    # a CPU client's ops carry no scope: the SOCKET readers find nothing
    for name in SOCKET_METRICS:
        assert harness.metric_reader(name)(ctx) is None
    assert read({**ctx, "step_events": [{"kind": "mixed"}]}) is None
    assert read({**ctx, "trace": None}) is None


def test_the_socket_metrics_read_their_scopes_per_decode_step():
    pd, paths = _traced({f"op{i}": f"jit(step)/while/body/{s}/x:"
                         for i, s in enumerate(
                             ("socket.score", "socket.score/socket.hash",
                              "socket.select", "socket.gather",
                              "socket.gather", "socket.attend",
                              "layer.mlp"))})
    red = scopes.breakdown(pd, paths)
    ctx = {"trace": red, "step_events": [{"kind": "decode"}]}
    got = {m: harness.metric_reader(m)(ctx) for m in SOCKET_METRICS}
    # 10 ns an op, one program in the window
    assert got == pytest.approx({"socket_score_ms.decode": 2e-5,
                                 "socket_select_ms.decode": 1e-5,
                                 "socket_attend_ms.decode": 3e-5})
    mixed = {**ctx, "step_events": [{"kind": "decode"}, {"kind": "mixed"}]}
    assert all(harness.metric_reader(m)(mixed) is None
               for m in SOCKET_METRICS)
