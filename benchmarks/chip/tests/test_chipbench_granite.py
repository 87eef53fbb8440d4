"""The granitemoehybrid block and its plain reference: the weight layout
and a decode step's counts at the published widths are pinned (the held
experts a step reads, the Mamba state it reads and writes); at a tiny
size on the CPU a run through the harness is ``correct`` and the control
(the reference in bfloat16, in the program's place) is not; the two
per-layer metrics of the block read their scopes' device time."""

import json
import re
import sys
import time
from pathlib import Path
from types import SimpleNamespace as NS

REPO = Path(__file__).resolve().parents[3]
sys.path[:0] = [str(REPO), str(REPO / "src")]

import jax  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

from benchmarks.chip import (flops, harness, model, scopes,  # noqa: E402
                             traffic)
from benchmarks.chip.references import \
    granitemoehybrid_socket as ref  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"
CELL = "tiny-granite.decode"
GRANITE = model.load_config("granite-4.0-h-small")


def _cell():
    manifest = json.loads((DATA / "manifest.json").read_text())
    manifest["workloads"].append({"name": CELL, "config": "tiny-granite",
                                  "traffic": "tiny-decode", "chips": 1,
                                  "why": "two sessions on the tiny hybrid"})
    return harness.load_cell(CELL, manifest, root=DATA)


def test_the_granite_weights_have_the_published_widths():
    shapes = model.block(GRANITE).weight_shapes(GRANITE)
    assert len(shapes) == 169
    got = {k: v for k, v in shapes.items()
           if k.startswith(("embed", "groups/slot_0/", "groups/slot_5/"))}
    want = {
        "embed/table": ((100352, 4096), "bfloat16", 1 / 384),
        "groups/slot_0/mamba/in_proj": ((1, 4096, 16768), "bfloat16",
                                        4096 ** -0.5),
        "groups/slot_0/mamba/out_proj": ((1, 8192, 4096), "bfloat16",
                                         8192 ** -0.5),
        "groups/slot_0/mamba/conv_w": ((1, 8448, 4), "bfloat16", 0.5),
        "groups/slot_0/mamba/conv_b": ((1, 8448), "bfloat16", "zeros"),
        "groups/slot_0/mamba/A_log": ((1, 128), "float32", 1.0),
        "groups/slot_0/mamba/dt_bias": ((1, 128), "float32", "zeros"),
        "groups/slot_0/mamba/D": ((1, 128), "float32", "ones"),
        "groups/slot_0/mamba/norm_scale": ((1, 8192), "float32", "ones"),
        "groups/slot_5/attn/wq": ((1, 4096, 32, 128), "bfloat16",
                                  (4096 * 128 ** 0.5 / 128) ** -0.5),
        "groups/slot_5/attn/wk": ((1, 4096, 8, 128), "bfloat16",
                                  (4096 * 128 ** 0.5 / 128) ** -0.5),
        "groups/slot_5/attn/wv": ((1, 4096, 8, 128), "bfloat16",
                                  4096 ** -0.5),
        "groups/slot_5/attn/wo": ((1, 32, 128, 4096), "bfloat16",
                                  4096 ** -0.5),
        "groups/slot_5/attn/hash_w": ((1, 60, 10, 128), "float32", 1.0),
    }
    for slot in ("groups/slot_0/", "groups/slot_5/"):
        want.update({
            slot + "norm_mix/scale": ((1, 4096), "float32", "zeros"),
            slot + "norm_mlp/scale": ((1, 4096), "float32", "zeros"),
            slot + "moe/router": ((1, 4096, 72), "float32", 4096 ** -0.5),
            slot + "moe/w_gate": ((1, 18, 4096, 768), "bfloat16",
                                  4096 ** -0.5),
            slot + "moe/w_up": ((1, 18, 4096, 768), "bfloat16",
                                4096 ** -0.5),
            slot + "moe/w_down": ((1, 18, 768, 4096), "bfloat16",
                                  768 ** -0.5),
            slot + "moe/shared/w_gate": ((1, 4096, 1536), "bfloat16",
                                         4096 ** -0.5),
            slot + "moe/shared/w_up": ((1, 4096, 1536), "bfloat16",
                                       4096 ** -0.5),
            slot + "moe/shared/w_down": ((1, 1536, 4096), "bfloat16",
                                         1536 ** -0.5)})
    assert got == want
    size = {"bfloat16": 2, "float32": 4}
    assert sum(int(np.prod(s)) * size[d] for s, d, _ in shapes.values()) \
        == 6534610944


def test_the_granite_program_config_is_the_published_block():
    blk = model.block(GRANITE)
    geo = json.loads((harness.HERE / "cells" /
                      "granite-4.0-h-small.decode-32k.json").read_text())
    cfg = blk.program_config(GRANITE, serving=geo)
    assert [s.kind for s in cfg.layer_specs] == \
        ["mamba"] * 5 + ["attn"] + ["mamba"] * 4
    assert {s.mlp for s in cfg.layer_specs} == {"moe"}
    assert (cfg.num_experts, cfg.num_experts_per_tok, cfg.held_experts,
            cfg.first_held_expert, cfg.expert_ff,
            cfg.shared_expert_d_ff) == (72, 10, 18, 0, 768, 1536)
    assert (cfg.use_rope, cfg.attention_multiplier,
            cfg.embedding_multiplier, cfg.residual_multiplier,
            cfg.logits_scaling) == (False, 1 / 128, 12.0, 0.22, 16.0)
    assert (cfg.d_inner, cfg.ssm_heads, cfg.ssm_state, cfg.ssm_chunk,
            cfg.tie_embeddings) == (8192, 128, 128, 256, True)
    assert cfg.serving.max_context == 32768
    assert geo["pool_blocks"] == traffic.pool_blocks(
        traffic.load_mix("decode-32k"), geo["block_size"])
    # the count of what this chip holds matches the layout made for it
    assert cfg.param_count() == sum(
        int(np.prod(s[1:] if k.startswith("groups") else s))
        for k, (s, _, _) in blk.weight_shapes(GRANITE).items()
        if not k.endswith("hash_w"))
    c = dict(GRANITE, layer_types=["attention"] * 10)
    with pytest.raises(ValueError):
        blk.program_config(dict(c, position_embedding_type="rope"),
                           serving=geo)


# (flops, bytes) of a decode step at the published widths
GRANITE_STEPS = [([16384] * 8, (34491219968.0, 6258569755.727179)),
                 ([30720], (4885383168.0, 3718241936.0)),
                 ([], (0.0, 3137224704))]


@pytest.mark.parametrize("lengths,want", GRANITE_STEPS)
def test_granite_step_counts(lengths, want):
    assert flops.step(GRANITE, lengths) == pytest.approx(want, rel=1e-12)


def test_a_step_reads_the_held_experts_its_tokens_route_to():
    blk = model.block(GRANITE)
    b0, b1, b8 = (blk.step(GRANITE, [16384] * n)[1] for n in (0, 1, 8))
    expert = 3 * 4096 * 768 * 2
    state = 4 * 128 * 64 * 128 + 3 * 8448 * 2
    assert blk.state_bytes(GRANITE) == state == 4244992
    socket = flops.decode_token(GRANITE, 16384)[1]
    row = 4096 * 2
    # B tokens read 18 (1 - (62/72)^B) of the 18 held experts in each of
    # the 10 layers, and each Mamba layer's state twice a token
    for b, got in ((1, b1), (8, b8)):
        held = 18 * (1 - (1 - 10 / 72) ** b)
        assert got - b0 == pytest.approx(
            10 * held * expert + b * (9 * 2 * state + socket + row))
    assert 18 * (1 - (62 / 72) ** 8) == pytest.approx(12.56, abs=0.01)
    # a token multiplies by the 10 * 18 / 72 = 2.5 held experts it routes
    # to here on average, and the shared expert
    f1, f2 = blk.step(GRANITE, [100])[0], blk.step(GRANITE, [100, 100])[0]
    assert f2 == 2 * f1
    assert blk.matmul_params(GRANITE) == 10 * (
        4096 * 72 + 2.5 * 3 * 4096 * 768 + 3 * 4096 * 1536) + 9 * (
        4096 * 16768 + 8192 * 4096) + 4096 * 48 * 128 + 4096 * 4096 \
        + 4096 * 100352


def test_the_tiny_hybrids_made_weights_have_the_programs_layout():
    from repro.models import param as pm
    from repro.models import transformer as tfm
    c = model.load_config("tiny-granite", DATA)
    blk = model.block(c, DATA)
    assert Path(blk.__file__) == DATA / "blocks" / "granitemoehybrid.py"
    cfg = blk.program_config(c, serving=_cell().geometry)
    program = jax.eval_shape(
        lambda: pm.unbox(tfm.init_model(cfg, jax.random.PRNGKey(0))))
    made = jax.eval_shape(lambda: model.make_weights(c, 3, DATA))

    def layout(tree):
        return {jax.tree_util.keystr(p): (x.shape, x.dtype) for p, x in
                jax.tree_util.tree_leaves_with_path(tree)}
    assert layout(made) == layout(program)


def _run(cell, seed):
    with jax.default_matmul_precision("float32"):
        return harness.run_cell(cell, seed, 0.5, trace=False,
                                t_start=time.perf_counter())


@pytest.mark.parametrize("seed", [3, 2 ** 33 + 4])
def test_a_tiny_hybrid_run_agrees_with_the_reference(seed):
    out = _run(_cell(), seed)
    assert out["correct"], out["checks"]
    assert all(c["value"] == 0.0 for c in out["checks"].values())
    assert out["attempted"] == 2 and out["failed"] == 0
    assert out["info"]["window_compiles"] == 0
    assert out["info"]["compared_tokens"] > 2


def test_the_tiny_hybrids_control_is_not_correct():
    cell, seed = _cell(), 1
    limits = cell.geometry["limits"]
    with jax.default_matmul_precision("float32"):
        w = model.make_weights(cell.config, seed, DATA)
        engine = harness.build_engine(cell, w)
        sessions = harness.requests_of(traffic.generate(
            cell.mix, seed, cell.config["vocab_size"]))
        harness.prefill(engine, sessions)
        win = harness.serve_window(engine, sessions, 0.5)
        harness.release(engine)
        reqs = harness.sample_requests(win, seed, limits["sample_requests"])
        program = harness.logit_gaps(cell, w, reqs)
        control = np.concatenate([ref.control_gaps(
            cell.config, w, r.prompt, r.generated) for r in reqs])
    for name, fn in harness.GAP_STATISTICS.items():
        assert fn(program) <= limits[name] < fn(control), name


NEW_SCOPES = {"mamba_ms.decode": ("mamba.proj", "mamba.scan",
                                  "mamba.state"),
              "moe_ms.decode": ("moe.route", "moe.experts", "moe.shared")}


def test_the_program_names_the_hybrid_scopes():
    named = set()
    for path in (REPO / "src" / "repro").rglob("*.py"):
        named |= set(re.findall(r"named_scope\(\s*[\"']([^\"']*)[\"']",
                                path.read_text()))
    for names in NEW_SCOPES.values():
        assert set(names) <= named
        assert all(scopes.SCOPE.fullmatch(n) for n in names)


def test_the_hybrid_metrics_read_their_scopes_per_decode_step():
    paths = {f"op{i}": f"jit(step)/while/body/{s}/x:" for i, s in enumerate(
        ("layer.mlp/moe.route", "layer.mlp/moe.experts",
         "layer.mlp/moe.experts", "layer.mlp/moe.shared", "mamba.proj",
         "mamba.scan", "mamba.scan", "mamba.state", "socket.score",
         "layer.mlp"))}
    ops = [(op, 10 * i, 10 * i + 10) for i, op in enumerate(paths)]
    end = 10 * len(ops)

    def plane(name, lines):
        return NS(name=name, lines=[
            NS(name=ln, events=[NS(name=n, start_ns=s, duration_ns=e - s)
                                for n, s, e in evs])
            for ln, evs in lines.items()])
    pd = NS(planes=[
        plane("/device:TPU:0", {"XLA Ops": ops,
                                "XLA Modules": [("jit_step", 0, end)]}),
        plane("/host:CPU", {"python": [("decode", -5, end)]})])
    red = scopes.breakdown(pd, {"/device:TPU:0": paths})
    ctx = {"trace": red, "step_events": [{"kind": "decode"}]}
    got = {m: harness.metric_reader(m)(ctx) for m in NEW_SCOPES}
    # 10 ns an op, one program in the window
    assert got == pytest.approx({"mamba_ms.decode": 4e-5,
                                 "moe_ms.decode": 4e-5})
    # a program without these scopes (the dense block's): no reading
    bare = scopes.breakdown(pd, {"/device:TPU:0": {
        op: "jit(step)/while/body/socket.score/x:" for op in paths}})
    assert all(harness.metric_reader(m)({**ctx, "trace": bare}) is None
               for m in NEW_SCOPES)
    mixed = {**ctx, "step_events": [{"kind": "decode"}, {"kind": "mixed"}]}
    assert all(harness.metric_reader(m)(mixed) is None for m in NEW_SCOPES)
