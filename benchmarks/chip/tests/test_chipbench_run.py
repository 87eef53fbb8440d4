"""A run end to end on the CPU at a tiny size, with the look for a chip
skipped: a sound run is ``correct``; the control (the reference one
precision below the configuration's, in the program's place) and each
fault planted in the served path are not.  The command itself refuses to
run without a TPU.

The tiny model states float32, so the program runs at float32 matmul
precision, the control is the bfloat16 reference, and a sound run's
gaps read 0 on these seeds."""

import json
import os
import subprocess
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parents[3]
sys.path[:0] = [str(REPO), str(REPO / "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

from benchmarks.chip import harness, model, traffic  # noqa: E402
from benchmarks.chip.references import dense_socket  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"
MANIFEST = json.loads((DATA / "manifest.json").read_text())


def _cell():
    return harness.load_cell("tiny.decode", MANIFEST, root=DATA)


def _run(cell, seed, fault=None):
    with jax.default_matmul_precision("float32"):
        return harness.run_cell(cell, seed, 0.5, trace=False,
                                t_start=time.perf_counter(), fault=fault)


def test_command_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "mistral-7b-v0.3.decode-long", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=REPO, env=env, capture_output=True,
        text=True, timeout=300)
    assert p.returncode == 2
    assert p.stdout == ""
    assert "needs 1 TPU" in p.stderr


@pytest.mark.parametrize("seed", [3, 2 ** 33 + 4])
def test_sound_run_is_correct(seed):
    out = _run(_cell(), seed)
    assert out["correct"], out["checks"]
    assert set(out["checks"]) == {"max_logit_gap", "mean_logit_gap"}
    assert all(c["value"] == 0.0 for c in out["checks"].values())
    assert list(out)[-1] == "checks"
    assert out["attempted"] == 2 and out["failed"] == 0
    assert out["info"]["window_compiles"] == 0
    assert {"setup_s", "output_tok_s"} == set(out["metrics"])


def _token_altered(engine):
    fn = engine._decode_fn
    vocab = engine.cfg.vocab_size

    def broken(*args):
        tok, keys, pages = fn(*args)
        return (tok + 1) % vocab, keys, pages
    engine._decode_fn = broken


def _state_unchanged(engine):
    fn = engine._decode_fn

    def broken(params, pages, *rest):
        tok, keys, _ = fn(params, jax.tree_util.tree_map(jnp.copy, pages),
                          *rest)
        return tok, keys, pages
    engine._decode_fn = broken


@pytest.mark.parametrize("fault", [_token_altered, _state_unchanged])
def test_a_fault_in_the_served_path_is_not_correct(fault):
    out = _run(_cell(), 3, fault=fault)
    assert not out["correct"]
    assert all(c["value"] > c["limit"] for c in out["checks"].values())


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_control_is_not_correct(seed):
    cell = _cell()
    limit = cell.geometry["limits"]["mean_logit_gap"]
    with jax.default_matmul_precision("float32"):
        w = model.make_weights(cell.config, seed)
        engine = harness.build_engine(cell, w)
        sessions = harness.requests_of(traffic.generate(
            cell.mix, seed, cell.config["vocab_size"]))
        harness.prefill(engine, sessions)
        win = harness.serve_window(engine, sessions, 0.5)
        harness.release(engine)
    reqs = harness.sample_requests(
        win, seed, cell.geometry["limits"]["sample_requests"])
    program = harness.logit_gaps(cell, w, reqs).mean()
    control = np.concatenate([dense_socket.control_gaps(
        cell.config, w, r.prompt, r.generated) for r in reqs]).mean()
    assert program <= limit < control
