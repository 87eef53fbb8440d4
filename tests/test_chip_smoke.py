"""``chip_smoke.py`` on the CPU: its phases at smoke size with interpreted
kernels, its refusal of a non-TPU device, a side-effect-free import,
and the compile-cache placement its entry points share."""

import os
import subprocess
import sys

import jax
import pytest

from repro.configs import get_config
from repro.launch.serve import configure_compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


def test_main_refuses_a_cpu_device(capsys):
    assert chip_smoke.main() == 2
    out, err = capsys.readouterr()
    assert out == ""                     # no result line, no JSON at all
    assert "'cpu'" in err


def test_import_touches_no_device_and_sets_no_cache():
    code = ("import jax\n"
            "from jax._src import xla_bridge\n"
            "before = jax.config.jax_compilation_cache_dir\n"
            "import chip_smoke\n"
            "assert not xla_bridge._backends, xla_bridge._backends\n"
            "assert jax.config.jax_compilation_cache_dir == before\n")
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = os.pathsep.join(
        [REPO, os.path.join(REPO, "src"), env.get("PYTHONPATH", "")])
    proc = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr


@pytest.mark.parametrize("env_dir", [None, "from-env"])
def test_compile_cache_placement(monkeypatch, tmp_path, env_dir):
    prev = jax.config.jax_compilation_cache_dir
    try:
        if env_dir is None:
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
            got = configure_compile_cache(tmp_path)
            assert got == str(tmp_path.resolve() / ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == got
        else:
            path = str(tmp_path / env_dir)
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", path)
            assert configure_compile_cache(tmp_path) == path
            assert jax.config.jax_compilation_cache_dir == prev
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


def test_engine_config_keeps_published_widths():
    cfg = chip_smoke.engine_config(num_blocks=3)
    full = get_config(chip_smoke.ARCH)
    for f in ("d_model", "num_heads", "num_kv_heads", "head_dim", "d_ff",
              "vocab_size"):
        assert getattr(cfg, f) == getattr(full, f), f
    assert (cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
            cfg.d_ff, cfg.vocab_size) == (4096, 32, 8, 128, 16384, 256000)
    assert cfg.num_layers == chip_smoke.LAYERS < full.num_layers
    assert cfg.param_dtype == cfg.compute_dtype == "bfloat16"
    sv = cfg.serving
    assert sv.block_size * sv.max_blocks_per_seq == 8192
    assert max(chip_smoke.PROMPT_LENS) + chip_smoke.NEW_TOKENS \
        <= sv.max_context
    # every prompt is long enough that ceil(n / sparsity) clears the
    # forced sink + window floor, so SOCKET selects beyond the floor
    s = cfg.socket
    assert min(chip_smoke.PROMPT_LENS) / s.sparsity > \
        s.sink_tokens + s.window_tokens


def test_kernel_phase_smoke():
    report = chip_smoke.kernel_phase(
        batch=2, kv_heads=2, group=2, head_dim=32, block_size=8,
        blocks_per_seq=4, num_planes=6, num_tables=12, sparsity=4.0,
        sink=4, window=4, min_k=4, interpret=True)
    assert report["ok"], report
    assert report["rows_exact_selection"] == 1.0
    assert report["interpret"] is True


def test_engine_phase_smoke():
    cfg = get_config(chip_smoke.ARCH).smoke().replace(
        param_dtype="bfloat16", compute_dtype="bfloat16")
    params = chip_smoke.init_params(cfg)
    prompts = chip_smoke.make_prompts(cfg.vocab_size, lens=(21, 34, 40, 47))
    report = chip_smoke.engine_phase(cfg, params, prompts, new_tokens=8,
                                     expect_kernel=False)
    assert report["ok"], report["gates"]
    assert set(report["gates"]) == {"socket_complete",
                                    "socket_fused_complete",
                                    "same_first_token"}
