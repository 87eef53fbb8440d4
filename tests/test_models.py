"""Component-level model tests: MoE dispatch vs dense reference, Mamba
causality/decode equivalence, attention variants, RoPE."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import LayerSpec, ModelConfig
from repro.models import attention as attn
from repro.models import mamba as mb
from repro.models import moe as moe_mod
from repro.models import param as pm
from repro.models.layers import apply_rope


# ------------------------------------------------------------------- MoE

def _moe_cfg(**kw):
    base = dict(name="t", family="moe", d_model=32, d_ff=64, num_experts=4,
                num_experts_per_tok=2, capacity_factor=4.0,
                mlp_activation="swiglu")
    base.update(kw)
    return ModelConfig(**base)


def _moe_reference(cfg, params, x):
    """Dense loop-over-experts oracle (no capacity, exact top-k)."""
    b, t, d = x.shape
    x2 = x.reshape(-1, d)
    logits = x2 @ params["router"]
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, cfg.num_experts_per_tok)
    top_p = top_p / top_p.sum(-1, keepdims=True)
    out = jnp.zeros_like(x2)
    for e in range(cfg.num_experts):
        gate = jax.nn.silu(x2 @ params["w_gate"][e])
        h = gate * (x2 @ params["w_up"][e])
        y_e = h @ params["w_down"][e]
        for slot in range(cfg.num_experts_per_tok):
            w = jnp.where(top_i[:, slot] == e, top_p[:, slot], 0.0)
            out = out + w[:, None] * y_e
    return out.reshape(b, t, d)


def test_moe_matches_dense_reference(rng):
    cfg = _moe_cfg()
    params = pm.unbox(moe_mod.init_moe(cfg, rng))
    x = jax.random.normal(jax.random.fold_in(rng, 1), (2, 16, 32))
    y, aux = moe_mod.apply_moe(cfg, params, x)
    ref = _moe_reference(cfg, params, x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref), atol=2e-5)
    assert float(aux["moe_lb_loss"]) > 0


def test_moe_decode_dropless(rng):
    """T=1 must be exactly dropless regardless of capacity_factor."""
    cfg = _moe_cfg(capacity_factor=0.01)
    params = pm.unbox(moe_mod.init_moe(cfg, rng))
    x = jax.random.normal(jax.random.fold_in(rng, 2), (8, 1, 32))
    y, _ = moe_mod.apply_moe(cfg, params, x)
    ref = _moe_reference(cfg, params, x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref), atol=2e-5)


def test_moe_capacity_drops_are_first_come_first_served(rng):
    """Stable-sort dispatch: earlier flat tokens keep their slots when
    later tokens are appended (the causality property)."""
    cfg = _moe_cfg(capacity_factor=0.6)
    params = pm.unbox(moe_mod.init_moe(cfg, rng))
    x = jax.random.normal(jax.random.fold_in(rng, 3), (1, 24, 32))
    y_full, _ = moe_mod.apply_moe(cfg, params, x)
    y_short, _ = moe_mod.apply_moe(cfg, params, x[:, :16])
    # capacity differs (N changed) — compare against same-capacity slice:
    # instead check prefix invariance with appended tokens at SAME capacity
    cfg2 = _moe_cfg(capacity_factor=cfg.capacity_factor * 24 / 16)
    y_short2, _ = moe_mod.apply_moe(cfg2, params, x[:, :16])
    np.testing.assert_allclose(np.asarray(y_full[:, :16]),
                               np.asarray(y_short2), atol=2e-5)


# ------------------------------------------------------------------ Mamba

def _mamba_cfg():
    return get_config("mamba2-780m").smoke()


def test_mamba_is_causal(rng):
    cfg = _mamba_cfg()
    params = pm.unbox(mb.init_mamba(cfg, rng))
    x = jax.random.normal(jax.random.fold_in(rng, 1), (2, 40, cfg.d_model))
    y_full = mb.mamba_train(cfg, params, x)
    y_pre = mb.mamba_train(cfg, params, x[:, :24])
    np.testing.assert_allclose(np.asarray(y_full[:, :24]),
                               np.asarray(y_pre), atol=1e-4)


def test_mamba_decode_matches_train(rng):
    cfg = _mamba_cfg()
    params = pm.unbox(mb.init_mamba(cfg, rng))
    x = jax.random.normal(jax.random.fold_in(rng, 2), (2, 33, cfg.d_model))
    y_ref = mb.mamba_train(cfg, params, x)
    y_pre, state = mb.mamba_train(cfg, params, x[:, :32],
                                  return_state=True)
    y_step, _ = mb.mamba_decode(cfg, params, x[:, 32:33], state)
    np.testing.assert_allclose(np.asarray(y_step[:, 0]),
                               np.asarray(y_ref[:, 32]), atol=1e-4)


def test_mamba_chunk_size_invariance(rng):
    cfg = _mamba_cfg()
    params = pm.unbox(mb.init_mamba(cfg, rng))
    x = jax.random.normal(jax.random.fold_in(rng, 3), (1, 64, cfg.d_model))
    y16 = mb.mamba_train(cfg.replace(ssm_chunk=16), params, x)
    y32 = mb.mamba_train(cfg.replace(ssm_chunk=32), params, x)
    np.testing.assert_allclose(np.asarray(y16), np.asarray(y32), atol=1e-4)


# -------------------------------------------------------------- attention

def test_rope_preserves_norm_and_relativity(rng):
    x = jax.random.normal(rng, (1, 8, 2, 16))
    pos = jnp.broadcast_to(jnp.arange(8), (1, 8))
    y = apply_rope(x, pos, 10000.0)
    np.testing.assert_allclose(np.asarray(jnp.linalg.norm(y, axis=-1)),
                               np.asarray(jnp.linalg.norm(x, axis=-1)),
                               rtol=1e-5)
    # relative property: <rope(q,m), rope(k,n)> depends only on m-n
    q = jax.random.normal(jax.random.fold_in(rng, 1), (1, 1, 1, 16))
    k = jax.random.normal(jax.random.fold_in(rng, 2), (1, 1, 1, 16))
    def dot_at(m, n):
        qm = apply_rope(q, jnp.full((1, 1), m), 1e4)
        kn = apply_rope(k, jnp.full((1, 1), n), 1e4)
        return float(jnp.sum(qm * kn))
    assert abs(dot_at(3, 1) - dot_at(10, 8)) < 1e-4


def test_sliding_window_mask(rng):
    cfg = get_config("gemma3-27b").smoke().replace(sliding_window=8)
    params = pm.unbox(attn.init_attention(cfg, rng))
    b, t = 1, 32
    x = jax.random.normal(jax.random.fold_in(rng, 1), (b, t, cfg.d_model))
    pos = jnp.broadcast_to(jnp.arange(t), (b, t))
    y_local = attn.attention_train(cfg, params, x, pos, "local")
    # perturbing a token outside the window must not change the output
    x2 = x.at[:, 0].add(10.0)
    y2 = attn.attention_train(cfg, params, x2, pos, "local")
    np.testing.assert_allclose(np.asarray(y_local[:, 20:]),
                               np.asarray(y2[:, 20:]), atol=1e-4)
    # ...but it does under global attention
    y_g = attn.attention_train(cfg, params, x, pos, "global")
    y_g2 = attn.attention_train(cfg, params, x2, pos, "global")
    assert float(jnp.max(jnp.abs(y_g[:, 20:] - y_g2[:, 20:]))) > 1e-3


def test_local_ring_buffer_decode(rng):
    """Local-layer ring cache must equal masked-window dense attention."""
    cfg = get_config("gemma3-27b").smoke().replace(
        sliding_window=16, attention_backend="dense")
    params = pm.unbox(attn.init_attention(cfg, rng))
    b, t = 1, 40
    x = jax.random.normal(jax.random.fold_in(rng, 5), (b, t, cfg.d_model))
    pos = jnp.broadcast_to(jnp.arange(t), (b, t))
    y_ref = attn.attention_train(cfg, params, x, pos, "local")
    _, cache = attn.attention_prefill(cfg, params, x[:, :32], pos[:, :32],
                                      "local", capacity=64)
    cache = jax.tree_util.tree_map(lambda a: a[None], cache)  # layer axis
    y32, cache = attn.attention_decode(cfg, params, x[:, 32:33], cache,
                                       jnp.int32(32), "local", 0)
    np.testing.assert_allclose(np.asarray(y32[:, 0]),
                               np.asarray(y_ref[:, 32]), atol=2e-4)


def test_head_padding_is_exact(rng):
    """logical_pad_heads zero-pads q heads: same function, padded shapes."""
    cfg = get_config("musicgen-medium").smoke()
    cfg_pad = cfg.replace(logical_pad_heads=True)
    p1 = pm.unbox(attn.init_attention(cfg, rng))
    p2 = pm.unbox(attn.init_attention(cfg_pad, rng))
    assert p2["wq"].shape[1] % 16 == 0
    # padded columns of wq and rows of wo are zero
    h_real = cfg.num_heads
    assert float(jnp.abs(p2["wq"][:, h_real:]).max()) == 0.0
    assert float(jnp.abs(p2["wo"][h_real:]).max()) == 0.0
