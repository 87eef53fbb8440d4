"""granite-4.0-h's hybrid block on the served path, at a small size on
the CPU: Mamba-2 and NoPE attention in the published period (attention
sixth of ten), every layer an MoE of which this chip holds a share of
the experts plus a shared expert, and the four multipliers.

* Chunked prefill and then decode through the paged pool and the Mamba
  state rows agree with the plain float32 reference
  (``benchmarks/chip/references/granitemoehybrid_socket.py``), and the
  engine emits the tokens those logits pick.
* The parts that four expert shares compute, with the shared expert
  counted once, add up to the uncut layer.
* A prefill chunk whose tokens all route to the same experts drops none.
* With the new configuration fields at their defaults, today's programs
  lower to exactly what they lowered to before those fields existed.
"""

import hashlib
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(REPO)]

from benchmarks.chip import model  # noqa: E402
from benchmarks.chip.references import \
    granitemoehybrid_socket as ref  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.models import moe as moe_mod  # noqa: E402
from repro.models import param as pm  # noqa: E402
from repro.models import transformer as tfm  # noqa: E402
from repro.serving import Request, paged  # noqa: E402

GEOMETRY = {"block_size": 8, "pool_blocks": 40, "max_batch": 2,
            "max_blocks_per_seq": 16, "prefill_chunk": 16}

# granite-4.0-h-small's keys at a small size (widths and counts cut; the
# period, NoPE, the multipliers, top-k over a wider router than the
# experts held, the shared expert as published)
SMALL = {
    "name": "granite-small", "source": "a small granitemoehybrid",
    "block": "granitemoehybrid", "attention_bias": False,
    "attention_multiplier": 1 / 16, "embedding_multiplier": 12,
    "hidden_act": "silu", "hidden_size": 64, "intermediate_size": 32,
    "layer_types": ["mamba"] * 5 + ["attention"] + ["mamba"] * 4,
    "logits_scaling": 16, "mamba_chunk_size": 16, "mamba_conv_bias": True,
    "mamba_d_conv": 4, "mamba_d_head": 16, "mamba_d_state": 16,
    "mamba_expand": 2, "mamba_n_groups": 1, "mamba_n_heads": 8,
    "mamba_proj_bias": False, "num_attention_heads": 4,
    "num_experts_per_tok": 2, "num_hidden_layers": 10,
    "num_key_value_heads": 2, "num_local_experts": 4,
    "first_held_expert": 2, "position_embedding_type": "nope",
    "residual_multiplier": 0.22, "rms_norm_eps": 1e-6, "rope_theta": 10000,
    "shared_intermediate_size": 48, "tie_word_embeddings": True,
    "vocab_size": 256, "head_dim": 16, "torch_dtype": "float32",
    "socket": {"num_planes": 4, "num_tables": 6, "tau": 0.4,
               "sparsity": 4.0, "sink_tokens": 8, "window_tokens": 8,
               "min_k": 4, "selection": "kvhead"},
    "source_values": {"num_local_experts": 8},
}


def _cfg(c=SMALL):
    return model.block(c).program_config(c, serving=GEOMETRY)


def _served(cfg, params, prompt, steps):
    """Greedy generation through the served functions: the prompt in
    ``prefill_chunk``-token chunks into the pool and the slot's state
    rows, then one decode step a token.  Returns (tokens, logits at the
    positions that produced them)."""
    sv = cfg.serving
    pages = paged.init_paged_caches(cfg, sv)
    c, bs = sv.prefill_chunk, sv.block_size
    n_blocks = -(-(len(prompt) + steps) // bs)
    bt = np.zeros(sv.max_blocks_per_seq + c // bs, np.int32)
    bt[:n_blocks] = np.arange(1, n_blocks + 1)
    chunk = jax.jit(lambda p, pg, t, h, li: tfm.prefill_chunk(
        cfg, p, pg, t, bt_row=jnp.asarray(bt), slot=jnp.int32(0),
        history=h, last_index=li))
    for start in range(0, len(prompt), c):
        part = prompt[start:start + c]
        toks = np.zeros((1, c), np.int32)
        toks[0, :len(part)] = part
        logits, pages = chunk(params, pages, jnp.asarray(toks),
                              jnp.int32(start),
                              jnp.asarray([len(part) - 1], jnp.int32))
    out_logits = [np.asarray(logits[0, 0, :cfg.vocab_size])]
    tokens = [int(np.argmax(out_logits[-1]))]
    tables = np.zeros((sv.max_batch, sv.max_blocks_per_seq), np.int32)
    tables[0] = bt[:sv.max_blocks_per_seq]
    step = jax.jit(lambda p, pg, x, pos: tfm.decode_step(
        cfg, p, pg, x, pos, block_tables=jnp.asarray(tables)))
    for i in range(steps - 1):
        x = np.zeros((sv.max_batch, 1), np.int32)
        x[0, 0] = tokens[-1]
        pos = np.asarray([len(prompt) + i] + [0] * (sv.max_batch - 1),
                         np.int32)
        logits, pages = step(params, pages, jnp.asarray(x),
                             jnp.asarray(pos))
        out_logits.append(np.asarray(logits[0, 0, :cfg.vocab_size]))
        tokens.append(int(np.argmax(out_logits[-1])))
    return tokens, np.stack(out_logits)


@pytest.mark.parametrize("seed", [1, 2])
def test_prefill_then_decode_through_the_cache_matches_the_reference(seed):
    cfg = _cfg()
    prompt = np.random.default_rng(seed).integers(0, 256, 40).tolist()
    with jax.default_matmul_precision("float32"):
        w = model.make_weights(SMALL, seed)
        tokens, logits = _served(cfg, w, prompt, 24)
        want = np.asarray(ref.served_logits(SMALL, w, prompt, tokens))
        low = np.asarray(ref.served_logits(SMALL, w, prompt, tokens,
                                           mode="bf16"))
        from repro.serving.engine import ContinuousBatchingEngine
        engine = ContinuousBatchingEngine(cfg, params=w)
        req = Request(prompt=list(prompt), max_new_tokens=24)
        req.sample_key = np.zeros(2, np.uint32)
        engine.run([req], realtime=False)
    # the engine's steps pick what these logits pick
    assert req.generated == tokens
    # Tolerance: float32 programs of the same function differ by
    # accumulation order alone, ~1e-6 of the logits' spread; SOCKET picks
    # the same keys in both.  Rounding every product's operands to
    # bfloat16 (the control) moves them by ~1e-2 of it.
    spread = float(np.std(want))
    assert np.max(np.abs(logits - want)) < 1e-4 * spread
    assert np.max(np.abs(low - want)) > 1e-3 * spread


def _moe_cfg(**kw):
    base = dict(name="t", family="moe", d_model=32, num_experts=8,
                num_experts_per_tok=2, expert_d_ff=24,
                shared_expert_d_ff=40, mlp_activation="swiglu")
    base.update(kw)
    from repro.configs.base import ModelConfig
    return ModelConfig(**base)


@pytest.mark.parametrize("t", [1, 12])
def test_expert_shares_add_up_to_the_uncut_layer(t):
    whole = _moe_cfg()
    params = pm.unbox(moe_mod.init_moe(whole, jax.random.PRNGKey(0)))
    x = jax.random.normal(jax.random.PRNGKey(1), (3, t, 32))
    with jax.default_matmul_precision("float32"):
        y, _ = moe_mod.apply_moe(whole, params, x, dropless=True)
        shared = moe_mod.apply_shared(whole, params, x)
        parts = []
        for first in range(0, 8, 2):
            share = whole.replace(first_held_expert=first,
                                  num_held_experts=2)
            p = dict(params, **{k: params[k][first:first + 2]
                                for k in ("w_gate", "w_up", "w_down")})
            assert jax.tree_util.tree_map(jnp.shape, p) == \
                jax.tree_util.tree_map(jnp.shape, jax.eval_shape(
                    lambda: pm.unbox(moe_mod.init_moe(
                        share, jax.random.PRNGKey(0)))))
            part, _ = moe_mod.apply_moe(share, p, x, dropless=True)
            parts.append(part - shared)
    np.testing.assert_allclose(np.asarray(sum(parts) + shared),
                               np.asarray(y), atol=1e-5)
    # the routed output is spread over the shares, not held by one
    assert sum(float(jnp.max(jnp.abs(p))) > 1e-3 for p in parts) >= 2


def test_a_prefill_chunk_whose_tokens_share_their_experts_drops_nothing():
    # a zero router ties every logit: every token routes to experts 0
    # and 1, which this chip holds, so each takes the chunk's 16 tokens
    # where a capacity factor of 1.25 would keep 5
    c = dict(SMALL, first_held_expert=0)
    cfg = _cfg(c)
    prompt = np.random.default_rng(5).integers(0, 256, 32).tolist()
    with jax.default_matmul_precision("float32"):
        w = model.make_weights(c, 4)
        for slot in w["groups"].values():
            slot["moe"]["router"] = jnp.zeros_like(slot["moe"]["router"])
        tokens, logits = _served(cfg, w, prompt, 2)
        want = np.asarray(ref.served_logits(c, w, prompt, tokens))
        x = jax.random.normal(jax.random.PRNGKey(2), (1, 16, 64))
        moe = w["groups"]["slot_0"]["moe"]
        moe = jax.tree_util.tree_map(lambda a: a[0], moe)
        served, _ = moe_mod.apply_moe(cfg, moe, x, dropless=True)
        capped, _ = moe_mod.apply_moe(cfg.replace(capacity_factor=1.25),
                                      moe, x)
    np.testing.assert_allclose(logits, want, atol=1e-4 * np.std(want))
    # the same chunk under a training capacity would have dropped tokens
    assert float(jnp.max(jnp.abs(served - capped))) > 1e-3


# sha256 of the lowered programs (StableHLO, no debug info) at the
# commit before the fields ``use_rope``, ``attention_multiplier``,
# ``embedding_multiplier``, ``residual_multiplier``, ``logits_scaling``,
# ``expert_d_ff``, ``shared_expert_d_ff``, ``first_held_expert`` and
# ``num_held_experts`` existed; a change that alters these programs on
# purpose re-pins them
BEFORE = {
    "dense.decode":
        "b9652cd2fbe07e602ed0b83f2cdb6f90ccea5f1244199a704c51b80e1f066c79",
    "dense.mixed":
        "df4876fd58e15b70da464e3f748ee9c78779f65bb70123d2de1be4d8786928dd",
    "mixtral-8x22b.train":
        "4717b94c9e743a62e783ebb3791703cab485d6c7a23f62851952aa3208f8a728",
    "jamba-v0.1-52b.train":
        "35e80e3ad10d0d5f7c05aeb5187400600529fd16bb8a137fc3f8a30b01c3f6c0",
    "minitron-8b.train":
        "44b277ee2fb6cdcb7138e01a084592b4de3d8009988616963869fe260824ded9",
}


def _digest(lowered) -> str:
    return hashlib.sha256(lowered.as_text().encode()).hexdigest()


@pytest.mark.parametrize("name", sorted(BEFORE))
def test_the_new_fields_at_their_defaults_change_no_program(name):
    if name.startswith("dense."):
        from repro.serving.engine import ContinuousBatchingEngine
        data = REPO / "benchmarks/chip/tests/data"
        c = model.load_config("tiny", data)
        cfg = model.block(c, data).program_config(
            c, serving={**GEOMETRY, "pool_blocks": 30})
        eng = ContinuousBatchingEngine(cfg, rng=jax.random.PRNGKey(0))
        sv = cfg.serving
        b, i32 = sv.max_batch, jnp.int32
        dec = (jnp.zeros((b, 1), i32),
               jnp.zeros((b, sv.max_blocks_per_seq), i32),
               jnp.zeros((b,), i32), jnp.zeros((b,), bool))
        if name == "dense.decode":
            lowered = eng._decode_fn.lower(eng.params, eng.pages, eng._keys,
                                           *dec)
        else:
            lowered = eng._mixed_fn.lower(
                eng.params, eng.pages, eng._keys,
                jnp.zeros((1, sv.prefill_chunk), i32),
                jnp.zeros((eng._chunk_bt_len(),), i32), i32(0), i32(0),
                jnp.zeros((1,), i32), jnp.asarray(False), *dec)
    else:
        cfg = get_config(name.rsplit(".", 1)[0]).smoke()
        params = pm.unbox(tfm.init_model(cfg, jax.random.PRNGKey(0)))
        lowered = jax.jit(lambda p, b: tfm.forward_train(cfg, p, b)).lower(
            params, {"tokens": jnp.zeros((2, 32), jnp.int32)})
    assert _digest(lowered) == BEFORE[name]
