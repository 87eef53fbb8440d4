"""The benchmark's arithmetic on synthetic records: percentiles and
rates over all samples, step composition, operation counts, the peak
table, the rule that sets a correctness limit and the trace reduction
(on a small CPU trace; the arithmetic only, no device metric comes from
it)."""

import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[3]
sys.path[:0] = [str(REPO), str(REPO / "src")]

import numpy as np  # noqa: E402
import pytest  # noqa: E402

from benchmarks.chip import flops, harness, model, peaks  # noqa: E402
from benchmarks.chip.calibrate import limit_from  # noqa: E402
from benchmarks.chip import trace as tr  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"


class _Req:
    def __init__(self, rid, prompt, walls):
        self.rid, self.prompt, self.token_walls = rid, [0] * prompt, walls
        self.generated = [1] * len(walls)


def _window(reqs, seconds, first=None, hooks=()):
    return harness.Window(
        seconds=seconds, requests=reqs,
        first_token=first or {r.rid: 0 for r in reqs}, hooks=list(hooks),
        step_events=[], compiles=0)


def test_tail_is_over_all_samples_not_medians_of_parts():
    # request 0: 100 gaps of 10 ms; request 1: 10 gaps of 100 ms
    a = _Req(0, 4, list(np.arange(101) * 0.010))
    b = _Req(1, 4, list(np.arange(11) * 0.100))
    out = harness.end_to_end(_window([a, b], 1.0))
    gaps = [0.010] * 100 + [0.100] * 10
    assert out["itl_p95_ms"] == pytest.approx(1e3 * np.percentile(gaps, 95))
    assert out["itl_p50_ms"] == pytest.approx(10.0)
    # 101 + 11 tokens, all by 1.0 s
    assert out["output_tok_s"] == pytest.approx(112.0)


def test_window_counts_only_what_it_holds():
    a = _Req(0, 4, [0.5, 0.9, 1.1, 1.5, 2.5])
    out = harness.end_to_end(_window([a], 2.0, first={0: 1}))
    # tokens 1..3 fall in (index >= 1, wall <= 2.0): 0.9, 1.1, 1.5
    assert out["output_tok_s"] == pytest.approx(3 / 2.0)
    assert out["itl_p50_ms"] == pytest.approx(300.0)   # gaps 0.2, 0.4


def test_window_steps_split_tokens_by_step():
    cfg = model.load_config("mistral-7b-v0.3")
    a = _Req(0, 100, [0.05, 0.15, 0.25])
    b = _Req(1, 300, [0.04, 0.16, 0.26])
    win = _window([a, b], 0.3, first={0: 0, 1: 1}, hooks=[0.1, 0.2, 0.3])
    steps = harness.window_steps(cfg, win)
    assert [s["kind"] for s in steps] == ["decode"] * 3
    # token 0 of a request came from its prefill, not from a decode step
    assert [s["occupancy"] for s in steps] == [0, 2, 2]
    # token i of a request decodes at context length prompt + i
    f, b_ = flops.step(cfg, [102, 302])
    assert steps[2]["flops"] == pytest.approx(f)
    assert steps[2]["bytes"] == pytest.approx(b_)


@pytest.mark.parametrize("name,root", [("mistral-7b-v0.3", None),
                                       ("tiny", DATA)])
def test_weight_flops_are_twice_the_counted_parameters(name, root):
    root = root or model.HERE
    c = model.load_config(name, root)
    blk = model.block(c, root)
    counted = blk.matmul_params(c)
    assert blk.weight_flops_per_token(c) == 2 * counted
    assert blk.weight_bytes(c) == 2 * counted


def test_weight_flops_match_the_made_weights():
    c = model.load_config("tiny", root=DATA)
    w = model.make_weights(c, 3, DATA)
    import jax
    leaves = jax.tree_util.tree_leaves_with_path(w)
    counted = sum(x.size for p, x in leaves
                  if jax.tree_util.keystr(p).split("'")[-2]
                  in ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down",
                      "head"))
    assert model.block(c, DATA).weight_flops_per_token(c) == 2 * counted


def test_decode_token_counts_follow_the_budget():
    c = model.load_config("mistral-7b-v0.3")
    s = c["socket"]
    assert flops.budget(s, 300) == 256          # sink + window floor
    assert flops.budget(s, 8192) == 820         # ceil(n / sparsity)
    assert flops.hash_words(s) == 20            # 600 bits in whole tables
    f1, b1 = flops.decode_token(c, 4096)
    f2, b2 = flops.decode_token(c, 8192)
    assert f2 > f1 and b2 > b1


def test_unknown_device_kind_raises():
    assert peaks.peak("TPU v5 lite")["flops_bf16"] == 197e12
    with pytest.raises(KeyError, match="no published peak"):
        peaks.peak("cpu")


def test_interval_union_gaps_and_attribution():
    merged = tr.union([(5, 8), (0, 2), (1, 3), (7, 12)], 1, 11)
    assert merged == [(1, 3), (5, 11)]
    assert tr.gaps(merged, 0, 12) == [(0, 1), (3, 5), (11, 12)]
    spans = [("decode", 0, 10), ("bench.hook", 3, 5)]
    assert tr.span_at(spans, 4) == "bench.hook"
    assert tr.span_at(spans, 8) == "decode"
    assert tr.span_at(spans, 11) == "host:unannotated"
    mods = [("jit_step", 1, 4), ("small", 2, 3), ("jit_step", 6, 9)]
    steps = [("decode", 0, 1), ("mixed", 5, 6)]
    got = tr.step_device_seconds(mods, steps)
    assert got == {"decode": [pytest.approx(3e-9)],
                   "mixed": [pytest.approx(3e-9)]}


def test_reduction_of_a_recorded_trace():
    pd, _ = tr.load(str(DATA / "cpu.xplane.pb"))
    red = tr.reduce(pd, span_names=("bench.hook",),
                    device_prefix="/host:CPU",
                    ops_line="tf_XLAPjRtCpuClient",
                    modules_line="tf_XLAPjRtCpuClient")
    assert red["devices"] == 1 and red["steps"] == 3
    assert 0 < red["busy_s"] <= red["window_s"]
    assert red["idle_share"] == pytest.approx(
        1 - red["busy_s"] / red["window_s"])
    idle = sum(red["idle_by_span"].values())
    assert idle == pytest.approx(red["window_s"] - red["busy_s"], rel=1e-6)
    # three 5 ms sleeps, each in its own span
    assert red["idle_by_span"]["bench.hook"] >= 0.015
    ops = [v for _, v in red["top_ops"]]
    assert ops == sorted(ops, reverse=True)
    assert all(name in ("decode", "bench.hook", "host:unannotated")
               for name, _ in red["idle_gaps"])
    assert len(red["step_device_s"]["decode"]) == 3
    # a trace with no device plane reduces to nothing
    assert tr.reduce(pd, device_prefix="/device:TPU:") is None


@pytest.mark.parametrize("control, controls, want", [
    ({"max": 0.5, "mean": 0.05}, 3, ("max_logit_gap", 0.34)),
    ({"max": 0.25, "mean": 0.05}, 3, ("mean_logit_gap", 0.032)),
    ({"max": 0.2, "mean": 0.01}, 3, None),
    ({"max": 0.5, "mean": 0.05}, 2, None),
])
def test_limit_lies_between_its_readings(control, controls, want):
    rows = [{"program": {"max": 0.1, "mean": 0.004}} for _ in range(12)]
    for r in rows[:controls]:
        r["control"] = control
    assert limit_from(rows) == want
