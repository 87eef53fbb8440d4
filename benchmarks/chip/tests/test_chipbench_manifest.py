"""The manifest resolves: every cell of BENCHMARK.json finds its
configuration, traffic mix, geometry and metric readers by name, and a
cell that exists only as added files loads by name too."""

import json
import re
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[3]
sys.path[:0] = [str(REPO), str(REPO / "src")]

import pytest  # noqa: E402

from benchmarks.chip import harness, model, traffic  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
MANIFEST = harness.load_manifest()


@pytest.mark.parametrize("cell", [w["name"] for w in MANIFEST["workloads"]])
def test_every_cell_resolves_by_name(cell):
    c = harness.load_cell(cell, MANIFEST)
    assert c.config["name"] in {x["name"] for x in MANIFEST["configs"]}
    assert c.geometry["attention_backend"] == "socket"
    assert c.end_to_end and c.per_layer
    assert any(m["name"] == "setup_s" for m in c.end_to_end)
    for m in c.per_layer:
        assert callable(harness.metric_reader(m["name"]))
    # the pool holds every session to the end of its turn and no more;
    # every session fits the ceiling, and each has a slot
    g = c.geometry
    assert g["pool_blocks"] == traffic.pool_blocks(c.mix, g["block_size"])
    assert max(c.mix["context_tokens"]) + c.mix["new_tokens"] <= \
        g["max_blocks_per_seq"] * g["block_size"]
    assert g["max_batch"] == len(c.mix["context_tokens"])
    # no session finishes in a window, even at twice the measured rate
    # (~15 tokens/s a session, TPU v5e)
    assert c.mix["new_tokens"] > 30 * MANIFEST["run_seconds"]


def test_manifest_names_and_files_follow_the_contract():
    m = MANIFEST
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in m[k]]
    assert all(NAME.match(n) for n in names)
    for k in ("configs", "workloads"):
        assert len({x["name"] for x in m[k]}) == len(m[k])
    metric_names = [x["name"] for x in m["end_to_end"] + m["per_layer"]]
    assert len(set(metric_names)) == len(metric_names)
    for c in m["configs"]:
        f = REPO / c["file"]
        assert f.is_file() and c["file"].startswith(m["paths"][0] + "/")
        cfg = json.loads(f.read_text())
        assert cfg["reduced"] == c["reduced"] and cfg["source"] == c["source"]
        assert set(cfg["reduced"]) <= set(cfg["source_values"])
    layers = {x["layer"] for x in m["per_layer"]}
    e2e = {x["name"] for x in m["end_to_end"]}
    assert all(x["moves"] in e2e for x in m["per_layer"])
    assert all(x["bound"] <= 0.25 for x in m["end_to_end"])
    assert layers


def test_a_cell_of_added_files_loads_purely_by_name():
    manifest = json.loads((DATA / "manifest.json").read_text())
    c = harness.load_cell("tiny.decode", manifest, root=DATA)
    assert c.config["hidden_size"] == 64
    assert c.mix["context_tokens"] == [40, 64]
    assert [m["name"] for m in c.per_layer] == ["occupancy.decode"]
    assert {m["name"] for m in c.end_to_end} == {"output_tok_s", "setup_s"}
    reader = harness.metric_reader("occupancy.decode", root=DATA)
    assert reader({"step_events": [{"occupancy": 2}, {"occupancy": 1}]}) \
        == 1.5
    with pytest.raises(KeyError):
        harness.load_cell("no.such-cell", manifest, root=DATA)


GEOMETRY = {"block_size": 16, "pool_blocks": 1665, "max_batch": 4,
            "max_blocks_per_seq": 512, "prefill_chunk": 256}


def test_program_config_follows_the_file():
    c = model.load_config("mistral-7b-v0.3")
    cfg = model.block(c).program_config(c, serving=GEOMETRY)
    assert (cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
            cfg.d_ff, cfg.vocab_size, cfg.num_layers, cfg.rope_theta) == \
        (4096, 32, 8, 128, 14336, 32768, 8, 1e6)
    assert not cfg.qk_norm and cfg.attention_backend == "socket"
    assert cfg.mlp_activation == "swiglu" and not cfg.tie_embeddings
    assert all(s.attn_type == "global" for s in cfg.layer_specs)
    s = cfg.socket
    assert not (s.use_paged_kernel or s.use_score_kernel
                or s.use_flash_decode)
    assert cfg.serving.max_context == 8192


@pytest.mark.parametrize("key,value", [("hidden_act", "relu2"),
                                       ("sliding_window", 4096),
                                       ("tie_word_embeddings", True),
                                       ("rms_norm_eps", 1e-5)])
def test_program_config_refuses_what_the_program_cannot_run(key, value):
    c = {**model.load_config("mistral-7b-v0.3"), key: value}
    with pytest.raises(ValueError, match="the program's dense block"):
        model.block(c).program_config(c, serving=GEOMETRY)
