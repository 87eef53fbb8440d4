"""A configuration's block is a file of its own, named by the
configuration's ``block`` key: the dense block reads exactly what it
read before it was a file (weights and step counts pinned), and a block
that only test data brings resolves by name and drives the program's
configuration, the weights and the window's step counts."""

import hashlib
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[3]
sys.path[:0] = [str(REPO), str(REPO / "src")]

import jax  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

from benchmarks.chip import flops, harness, model  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"
GEOMETRY = {"block_size": 8, "pool_blocks": 30, "max_batch": 2,
            "max_blocks_per_seq": 16, "prefill_chunk": 16}

# Read at the commit before blocks were files of their own.
TINY_WEIGHTS_SHA256 = \
    "9c2bf0eae58b2091c3a998c43f98048b9865c64de275077d41ddf3cc9284e60c"
MISTRAL_STEPS = [([102, 302], (7785529344.0, 3772039936)),
                 ([4480, 5248, 6016, 6784], (22445031424.0, 3950367232)),
                 ([], (0.0, 3758096384))]
MISTRAL_LAYOUT = [
    ("embed/head", (4096, 32768), "bfloat16", 0.015625),
    ("embed/table", (32768, 4096), "bfloat16", 0.015625),
    ("final_norm/scale", (4096,), "float32", "zeros"),
    ("groups/slot_0/attn/hash_w", (8, 60, 10, 128), "float32", 1.0),
    ("groups/slot_0/attn/wk", (8, 4096, 8, 128), "bfloat16", 0.015625),
    ("groups/slot_0/attn/wo", (8, 32, 128, 4096), "bfloat16", 0.015625),
    ("groups/slot_0/attn/wq", (8, 4096, 32, 128), "bfloat16", 0.015625),
    ("groups/slot_0/attn/wv", (8, 4096, 8, 128), "bfloat16", 0.015625),
    ("groups/slot_0/mlp/w_down", (8, 14336, 4096), "bfloat16",
     0.008351913809763262),
    ("groups/slot_0/mlp/w_gate", (8, 4096, 14336), "bfloat16", 0.015625),
    ("groups/slot_0/mlp/w_up", (8, 4096, 14336), "bfloat16", 0.015625),
    ("groups/slot_0/norm_mix/scale", (8, 4096), "float32", "zeros"),
    ("groups/slot_0/norm_mlp/scale", (8, 4096), "float32", "zeros"),
]


def _leaves(tree) -> dict:
    return {jax.tree_util.keystr(p): x
            for p, x in jax.tree_util.tree_leaves_with_path(tree)}


def test_dense_weights_are_bit_identical_to_before():
    c = model.load_config("tiny", DATA)
    h = hashlib.sha256()
    for path, x in sorted(_leaves(model.make_weights(c, 3, DATA)).items()):
        x = np.asarray(x)
        for part in (path, str(x.dtype), str(x.shape)):
            h.update(part.encode())
        h.update(x.tobytes())
    assert h.hexdigest() == TINY_WEIGHTS_SHA256


def test_mistral_weight_layout_is_as_before():
    c = model.load_config("mistral-7b-v0.3")
    got = sorted((k, tuple(shape), dt, std) for k, (shape, dt, std)
                 in model.block(c).weight_shapes(c).items())
    assert got == MISTRAL_LAYOUT


@pytest.mark.parametrize("lengths,want", MISTRAL_STEPS)
def test_mistral_step_counts_are_as_before(lengths, want):
    got = flops.step(model.load_config("mistral-7b-v0.3"), lengths)
    assert got == want
    assert type(got[1]) is int


@pytest.mark.parametrize("name", ["tiny", "tiny-moe"])
def test_the_made_weights_have_the_programs_layout(name):
    from repro.models import param as pm
    from repro.models import transformer as tfm
    c = model.load_config(name, DATA)
    cfg = model.block(c, DATA).program_config(c, serving=GEOMETRY)
    program = jax.eval_shape(
        lambda: pm.unbox(tfm.init_model(cfg, jax.random.PRNGKey(0))))
    made = jax.eval_shape(lambda: model.make_weights(c, 3, DATA))

    def layout(tree):
        return {k: (x.shape, x.dtype) for k, x in _leaves(tree).items()}
    assert layout(made) == layout(program)


def test_a_block_that_test_data_brings_resolves_by_name():
    c = model.load_config("tiny-moe", DATA)
    blk = model.block(c, DATA)
    assert Path(blk.__file__) == DATA / "blocks" / "local_global_moe.py"
    with pytest.raises(FileNotFoundError):
        model.block(c)                  # the benchmark has no such block
    cfg = blk.program_config(c, serving=GEOMETRY)
    assert [(s.attn_type, s.mlp) for s in cfg.layer_specs] == \
        [("local", "moe"), ("global", "moe")] * 2
    assert (cfg.num_experts, cfg.num_experts_per_tok,
            cfg.sliding_window) == (8, 2, 32)
    w = model.make_weights(c, 3, DATA)
    assert w["groups"]["slot_1"]["moe"]["w_up"].shape == (2, 8, 64, 32)

    # the window's steps are counted by this block, with no harness edit
    class Req:
        rid, prompt, generated = 0, [0] * 40, [1, 1, 1]
        token_walls = [0.05, 0.15, 0.25]
    win = harness.Window(seconds=0.3, requests=[Req()], first_token={0: 0},
                         hooks=[0.1, 0.2, 0.3], step_events=[], compiles=0)
    steps = harness.window_steps(c, win, DATA)
    assert [s["occupancy"] for s in steps] == [0, 1, 1]
    for s, lengths in zip(steps, ([], [41], [42])):
        assert (s["flops"], s["bytes"]) == blk.step(c, lengths)


def test_a_step_reads_the_experts_its_tokens_route_to():
    c = model.load_config("tiny-moe", DATA)
    blk = model.block(c, DATA)
    b0, b1, b2 = (blk.step(c, [40] * n)[1] for n in (0, 1, 2))
    experts = 4 * 2 * 3 * 64 * 32 * 2     # 4 layers, 2 experts, bf16
    # one token reads its 2 experts a layer; a second shares some
    assert experts < b1 - b0 < 1.2 * experts
    assert b2 - b1 < b1 - b0
    # operations follow the tokens: 2 experts each, never the union
    f1, f2 = blk.step(c, [40])[0], blk.step(c, [40, 40])[0]
    assert f2 == 2 * f1
