"""Shared neural layers: RMSNorm, RoPE, gated MLPs, embeddings."""

from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.distributed.sharding import lsc
from repro.models import param as pm

__all__ = ["rmsnorm", "init_rmsnorm", "apply_rope", "init_mlp", "apply_mlp",
           "init_embedding", "embed_tokens", "lm_head", "softcap"]


# --------------------------------------------------------------------- norm

def init_rmsnorm(d: int) -> Dict:
    return {"scale": pm.ones((d,), (None,))}


def rmsnorm(params: Dict, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + params["scale"].astype(jnp.float32))).astype(dtype)


def softcap(logits: jax.Array, cap: float) -> jax.Array:
    """Gemma-style logit soft-capping: cap * tanh(x / cap)."""
    if cap <= 0.0:
        return logits
    return cap * jnp.tanh(logits / cap)


# --------------------------------------------------------------------- rope

def apply_rope(x: jax.Array, positions: jax.Array,
               theta: float) -> jax.Array:
    """Rotary embedding.  x: (B, T, H, hd); positions: (B, T) int32."""
    hd = x.shape[-1]
    half = hd // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions[..., None].astype(jnp.float32) * freq  # (B,T,half)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    rotated = jnp.concatenate([x1 * cos - x2 * sin,
                               x2 * cos + x1 * sin], axis=-1)
    return rotated.astype(x.dtype)


# --------------------------------------------------------------------- mlp

def init_mlp(cfg: ModelConfig, rng: jax.Array) -> Dict:
    d, ff = cfg.d_model, cfg.d_ff
    k1, k2, k3 = jax.random.split(rng, 3)
    s_in = 1.0 / np.sqrt(d)
    s_out = 1.0 / np.sqrt(ff)
    dtype = jnp.dtype(cfg.param_dtype)
    return {
        "w_gate": pm.normal(k1, (d, ff), ("embed_w", "mlp"), stddev=s_in,
                            dtype=dtype),
        "w_up": pm.normal(k2, (d, ff), ("embed_w", "mlp"), stddev=s_in,
                          dtype=dtype),
        "w_down": pm.normal(k3, (ff, d), ("mlp", "embed_w"), stddev=s_out,
                            dtype=dtype),
    }


def apply_mlp(cfg: ModelConfig, params: Dict, x: jax.Array) -> jax.Array:
    cdt = jnp.dtype(cfg.compute_dtype)
    x = x.astype(cdt)
    gate = x @ params["w_gate"].astype(cdt)
    up = x @ params["w_up"].astype(cdt)
    act = jax.nn.gelu(gate, approximate=True) if cfg.mlp_activation == \
        "geglu" else jax.nn.silu(gate)
    h = lsc(act * up, "batch", "seq", "mlp")
    return h @ params["w_down"].astype(cdt)


# ------------------------------------------------------------ embeddings

def init_embedding(cfg: ModelConfig, rng: jax.Array) -> Dict:
    dtype = jnp.dtype(cfg.param_dtype)
    v = cfg.padded_vocab()
    out = {"table": pm.normal(rng, (v, cfg.d_model), ("vocab", "embed_w"),
                              stddev=1.0, dtype=dtype)}
    if not cfg.tie_embeddings:
        k2 = jax.random.fold_in(rng, 1)
        out["head"] = pm.normal(k2, (cfg.d_model, v), ("embed_w", "vocab"),
                                stddev=1.0 / np.sqrt(cfg.d_model),
                                dtype=dtype)
    return out


def embed_tokens(cfg: ModelConfig, params: Dict, tokens: jax.Array
                 ) -> jax.Array:
    cdt = jnp.dtype(cfg.compute_dtype)
    x = jnp.take(params["table"], tokens, axis=0).astype(cdt)
    mult = cfg.embedding_multiplier
    if mult is None:
        mult = np.sqrt(cfg.d_model)
    return lsc(x * jnp.asarray(mult, cdt), "batch", "act_seq", "embed")


def lm_head(cfg: ModelConfig, params: Dict, x: jax.Array) -> jax.Array:
    """Logits over the (padded) vocabulary, divided by
    ``cfg.logits_scaling``."""
    cdt = jnp.dtype(cfg.compute_dtype)
    w = params.get("head")
    if w is None:
        w = params["table"].T
    logits = x.astype(cdt) @ w.astype(cdt)
    if cfg.logits_scaling != 1.0:
        logits = logits / jnp.asarray(cfg.logits_scaling, cdt)
    return lsc(logits, "batch", "seq", "vocab")
