"""Mamba-2 (SSD — state-space duality) block [arXiv:2405.21060].

Chunked SSD forward for training/prefill (a port of the paper's
``ssd_minimal_discrete`` to jnp, organised as: intra-chunk quadratic part +
inter-chunk recurrent state passing via ``lax.scan``), plus an O(1)-state
decode step.  Layout: x (B, S, d_model) -> in_proj -> [z | xc | B | C | dt]
-> causal depthwise conv over (xc,B,C) -> SSD -> gated RMSNorm -> out_proj.
The projections are scoped ``mamba.proj``; the conv, the recurrence and
the gated norm ``mamba.scan``.

SOCKET does not apply to these layers: they keep no K/V, only an O(1)
state per sequence.
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.distributed.sharding import lsc
from repro.models import param as pm

__all__ = ["init_mamba", "mamba_train", "mamba_decode", "init_mamba_cache"]

N_GROUPS = 1  # B/C shared across heads (mamba2 default n_groups=1)


def _dims(cfg: ModelConfig):
    di = cfg.d_inner
    nh = cfg.ssm_heads
    st = cfg.ssm_state
    conv_dim = di + 2 * N_GROUPS * st
    return di, nh, st, conv_dim


def init_mamba(cfg: ModelConfig, rng: jax.Array) -> Dict:
    d = cfg.d_model
    di, nh, st, conv_dim = _dims(cfg)
    k1, k2, k3, k4 = jax.random.split(rng, 4)
    dtype = jnp.dtype(cfg.param_dtype)
    s = 1.0 / np.sqrt(d)
    proj_out = 2 * di + 2 * N_GROUPS * st + nh   # z, xc, B, C, dt
    # dt bias initialised so softplus(dt_bias) spans [1e-3, 1e-1]
    dt = jnp.exp(jax.random.uniform(k3, (nh,)) *
                 (np.log(0.1) - np.log(1e-3)) + np.log(1e-3))
    dt_bias = dt + jnp.log(-jnp.expm1(-dt))      # inverse softplus
    return {
        "in_proj": pm.normal(k1, (d, proj_out), ("embed_w", "ssm_inner"),
                             stddev=s, dtype=dtype),
        "conv_w": pm.normal(k2, (conv_dim, cfg.ssm_conv_width),
                            ("conv", None), stddev=0.5, dtype=dtype),
        "conv_b": pm.zeros((conv_dim,), ("conv",), dtype=dtype),
        "A_log": pm.constant(jnp.log(jnp.arange(1, nh + 1, dtype=jnp.float32)),
                             ("ssm_heads",)),
        "dt_bias": pm.constant(dt_bias.astype(jnp.float32), ("ssm_heads",)),
        "D": pm.ones((nh,), ("ssm_heads",)),
        "norm_scale": pm.ones((di,), ("ssm_inner",)),
        "out_proj": pm.normal(k4, (di, d), ("ssm_inner", "embed_w"),
                              stddev=1.0 / np.sqrt(di), dtype=dtype),
    }


def _split_proj(cfg: ModelConfig, proj: jax.Array):
    di, nh, st, _ = _dims(cfg)
    z, xc, bmat, cmat, dt = jnp.split(
        proj, [di, 2 * di, 2 * di + N_GROUPS * st,
               2 * di + 2 * N_GROUPS * st], axis=-1)
    return z, xc, bmat, cmat, dt


def _causal_conv(cfg: ModelConfig, params: Dict, u: jax.Array,
                 conv0=None) -> jax.Array:
    """Depthwise causal conv1d.  u: (B, S, C).

    ``conv0``: optional ``(B, K-1, C)`` carried tail of the *previous*
    segment's conv inputs (chunked prefill) — replaces the zero left-pad
    so a chunk's first outputs see exactly the history a whole-sequence
    run would."""
    w = params["conv_w"].astype(u.dtype)            # (C, K)
    k = w.shape[1]
    pad = jnp.pad(u, ((0, 0), (k - 1, 0), (0, 0))) if conv0 is None else \
        jnp.concatenate([conv0.astype(u.dtype), u], axis=1)
    out = jnp.zeros_like(u)
    for i in range(k):   # K=4: unrolled taps beat conv_general on TPU VPU
        out = out + pad[:, i:i + u.shape[1]] * w[None, None, :, i]
    return jax.nn.silu(out + params["conv_b"].astype(u.dtype))


def _segsum(x: jax.Array) -> jax.Array:
    """Stable segment-sum: out[..., i, j] = sum_{j<k<=i} x[..., k]."""
    t = x.shape[-1]
    cs = jnp.cumsum(x, axis=-1)
    seg = cs[..., :, None] - cs[..., None, :]
    mask = jnp.tril(jnp.ones((t, t), bool), k=0)
    return jnp.where(mask, seg, -jnp.inf)


def _ssd_chunked(cfg: ModelConfig, xh: jax.Array, dt: jax.Array,
                 a_coef: jax.Array, bmat: jax.Array, cmat: jax.Array,
                 h0: jax.Array | None = None):
    """Chunked SSD.  Shapes:
      xh (B,S,nh,hd) — inputs per head;  dt (B,S,nh) — discretization;
      a_coef (nh,) negative;  bmat/cmat (B,S,G,st).
    Returns (y (B,S,nh,hd), h_final (B,nh,hd,st)).
    """
    b, s, nh, hd = xh.shape
    st = bmat.shape[-1]
    q = min(cfg.ssm_chunk, s)
    s_orig = s
    if s % q:
        # zero-pad to a chunk multiple: dt=0 on padding => decay=1 and no
        # input contribution, so the carried state is unaffected.
        pad = q - s % q
        xh = jnp.pad(xh, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        bmat = jnp.pad(bmat, ((0, 0), (0, pad), (0, 0), (0, 0)))
        cmat = jnp.pad(cmat, ((0, 0), (0, pad), (0, 0), (0, 0)))
        s = s + pad
    nc = s // q

    # broadcast groups to heads
    bmat = jnp.repeat(bmat, nh // N_GROUPS, axis=2)   # (B,S,nh,st)
    cmat = jnp.repeat(cmat, nh // N_GROUPS, axis=2)

    xb = (xh * dt[..., None]).reshape(b, nc, q, nh, hd)
    da = (dt * a_coef[None, None, :]).reshape(b, nc, q, nh)  # (B,NC,Q,nh)
    bm = bmat.reshape(b, nc, q, nh, st)
    cm = cmat.reshape(b, nc, q, nh, st)

    da_t = jnp.transpose(da, (0, 1, 3, 2))            # (B,NC,nh,Q)
    da_cum = jnp.cumsum(da_t, axis=-1)                # within-chunk cumsum

    # 1. intra-chunk (quadratic) term
    l_mat = jnp.exp(_segsum(da_t))                    # (B,NC,nh,Q,Q)
    scores = jnp.einsum("bcqhs,bckhs->bchqk", cm, bm)  # (B,NC,nh,Q,Q)
    y_diag = jnp.einsum("bchqk,bchqk,bckhd->bcqhd",
                        scores, l_mat, xb)

    # 2. chunk-final states
    decay_states = jnp.exp(da_cum[..., -1:] - da_cum)  # (B,NC,nh,Q)
    states = jnp.einsum("bchq,bcqhs,bcqhd->bchds",
                        decay_states, bm, xb)          # (B,NC,nh,hd,st)

    # 3. inter-chunk recurrence over chunk index
    chunk_decay = jnp.exp(da_cum[..., -1])             # (B,NC,nh)

    def scan_fn(h, inp):
        st_c, dec = inp
        h_new = h * dec[..., None, None] + st_c
        return h_new, h

    if h0 is None:
        h0 = jnp.zeros((b, nh, hd, st), xh.dtype)
    h_final, h_prev = jax.lax.scan(
        scan_fn, h0,
        (jnp.moveaxis(states, 1, 0), jnp.moveaxis(chunk_decay, 1, 0)))
    h_prev = jnp.moveaxis(h_prev, 0, 1)                # (B,NC,nh,hd,st)

    # 4. contribution of carried state to each position
    state_decay = jnp.exp(da_cum)                      # (B,NC,nh,Q)
    y_off = jnp.einsum("bcqhs,bchds,bchq->bcqhd", cm, h_prev, state_decay)

    y = (y_diag + y_off).reshape(b, s, nh, hd)
    return y[:, :s_orig], h_final


def mamba_train(cfg: ModelConfig, params: Dict, x: jax.Array,
                h0=None, conv0=None, return_state: bool = False,
                last_index=None):
    """Full-sequence Mamba-2 block.  x: (B, S, d_model).

    ``last_index``: optional ``(B,)`` int32 of per-row last *real*
    positions (the serving engine pads prompts to a static bucket).
    Positions past it get ``dt = 0`` — decay ``exp(0·a) = 1`` and zero
    input, so they are exact identity steps and the returned ``ssm``
    state is the state at ``last_index``, bit-for-bit (the same trick
    the chunked SSD uses internally for chunk padding); the conv tail is
    likewise taken ending at ``last_index``.  Outputs at real positions
    are causal and unaffected.

    ``h0`` / ``conv0``: optional carried SSD state ``(B, nh, hd, st)``
    and conv tail ``(B, conv_width-1, conv_dim)`` from an earlier
    segment — chunked prefill runs the prompt through this function one
    chunk at a time, threading both through ``return_state``.  When the
    segment length is a multiple of ``ssm_chunk`` the chunk boundaries
    land on the SSD scan grid and the state trajectory is bit-identical
    to a whole-sequence run.
    """
    b, s, d = x.shape
    di, nh, st, conv_dim = _dims(cfg)
    cdt = jnp.dtype(cfg.compute_dtype)
    with jax.named_scope("mamba.proj"):
        proj = x.astype(cdt) @ params["in_proj"].astype(cdt)
    with jax.named_scope("mamba.scan"):
        y, z, conv_in, h_final = _scan_train(cfg, params, proj, h0, conv0,
                                             last_index)
        y = _gated_norm(cfg, params, y, z)
    with jax.named_scope("mamba.proj"):
        out = y @ params["out_proj"].astype(cdt)
    if return_state:
        with jax.named_scope("mamba.scan"):
            conv_tail = _conv_tail(cfg, conv_in, conv0, last_index)
        return out.astype(x.dtype), {"ssm": h_final,
                                     "conv": conv_tail.astype(cdt)}
    return out.astype(x.dtype)


def _conv_tail(cfg: ModelConfig, conv_in: jax.Array, conv0, last_index):
    """The last ``conv_width - 1`` conv inputs, ending at ``last_index``
    where it is given.  With ``conv0`` the carried tail prefixes conv_in,
    so a chunk whose real tokens number fewer than the tail reaches back
    into the previous chunk's rows instead of zeroing them."""
    kw = cfg.ssm_conv_width - 1
    ext = conv_in if conv0 is None else jnp.concatenate(
        [conv0.astype(conv_in.dtype), conv_in], axis=1)
    off = 0 if conv0 is None else kw
    if last_index is None:
        if ext.shape[1] < kw:
            return jnp.pad(ext, ((0, 0), (kw - ext.shape[1], 0), (0, 0)))
        return ext[:, -kw:]
    li = jnp.asarray(last_index, jnp.int32)
    idx = off + li[:, None] - kw + 1 + jnp.arange(kw, dtype=jnp.int32)
    tail = jnp.take_along_axis(ext, jnp.maximum(idx, 0)[..., None], axis=1)
    return jnp.where((idx >= 0)[..., None], tail, 0)


def _gated_norm(cfg: ModelConfig, params: Dict, y: jax.Array,
                z: jax.Array) -> jax.Array:
    """RMSNorm of y · silu(z) over the inner channels."""
    cdt = jnp.dtype(cfg.compute_dtype)
    y = y * jax.nn.silu(z)
    var = jnp.mean(jnp.square(y.astype(jnp.float32)), axis=-1, keepdims=True)
    return (y.astype(jnp.float32) * jax.lax.rsqrt(var + 1e-6) *
            params["norm_scale"].astype(jnp.float32)).astype(cdt)


def _scan_train(cfg: ModelConfig, params: Dict, proj: jax.Array, h0,
                conv0, last_index):
    """Conv and chunked SSD of a projected segment: (y, z, conv_in,
    final SSD state)."""
    b, s, _ = proj.shape
    di, nh, st, conv_dim = _dims(cfg)
    cdt = jnp.dtype(cfg.compute_dtype)
    z, xc, bmat, cmat, dt = _split_proj(cfg, proj)

    conv_in = jnp.concatenate([xc, bmat, cmat], axis=-1)  # (B,S,conv_dim)
    conv_out = _causal_conv(cfg, params, conv_in, conv0=conv0)
    xc, bmat, cmat = jnp.split(conv_out, [di, di + N_GROUPS * st], axis=-1)

    dt = jax.nn.softplus(dt.astype(jnp.float32) +
                         params["dt_bias"].astype(jnp.float32))
    if last_index is not None:
        li = jnp.asarray(last_index, jnp.int32)
        keep = jnp.arange(s, dtype=jnp.int32)[None, :] <= li[:, None]
        dt = dt * keep[..., None]
    a_coef = -jnp.exp(params["A_log"].astype(jnp.float32))

    xh = xc.reshape(b, s, nh, cfg.ssm_head_dim).astype(jnp.float32)
    bg = bmat.reshape(b, s, N_GROUPS, st).astype(jnp.float32)
    cg = cmat.reshape(b, s, N_GROUPS, st).astype(jnp.float32)
    if h0 is not None:
        h0 = h0.astype(jnp.float32)
    y, h_final = _ssd_chunked(cfg, xh, dt, a_coef, bg, cg, h0)
    y = y + xh * params["D"].astype(jnp.float32)[None, None, :, None]
    return y.reshape(b, s, di).astype(cdt), z, conv_in, h_final


def init_mamba_cache(cfg: ModelConfig, batch: int, dtype=None) -> Dict:
    dtype = dtype or jnp.dtype(cfg.compute_dtype)
    di, nh, st, conv_dim = _dims(cfg)
    return {
        "ssm": jnp.zeros((batch, nh, cfg.ssm_head_dim, st), jnp.float32),
        "conv": jnp.zeros((batch, cfg.ssm_conv_width - 1, conv_dim), dtype),
    }


def mamba_cache_logical_axes() -> Dict:
    return {"ssm": ("cache_batch", "ssm_heads", None, None),
            "conv": ("cache_batch", None, "conv")}


def mamba_decode(cfg: ModelConfig, params: Dict, x: jax.Array,
                 cache: Dict) -> Tuple[jax.Array, Dict]:
    """Single-token recurrent update.  x: (B, 1, d_model)."""
    cdt = jnp.dtype(cfg.compute_dtype)
    with jax.named_scope("mamba.proj"):
        proj = x[:, 0].astype(cdt) @ params["in_proj"].astype(cdt)
    with jax.named_scope("mamba.scan"):
        y, z, new_cache = _scan_decode(cfg, params, proj, cache)
        y = _gated_norm(cfg, params, y, z)
    with jax.named_scope("mamba.proj"):
        out = (y @ params["out_proj"].astype(cdt))[:, None]
    return out.astype(x.dtype), new_cache


def _scan_decode(cfg: ModelConfig, params: Dict, proj: jax.Array,
                 cache: Dict):
    """One token's conv and recurrent update: (y, z, new cache)."""
    b = proj.shape[0]
    di, nh, st, conv_dim = _dims(cfg)
    cdt = jnp.dtype(cfg.compute_dtype)
    z, xc, bmat, cmat, dt = _split_proj(cfg, proj)

    conv_in = jnp.concatenate([xc, bmat, cmat], axis=-1)        # (B, conv_dim)
    hist = jnp.concatenate([cache["conv"], conv_in[:, None]], axis=1)
    w = params["conv_w"].astype(cdt)                            # (C, K)
    conv_out = jnp.einsum("bkc,ck->bc", hist, w) + \
        params["conv_b"].astype(cdt)
    conv_out = jax.nn.silu(conv_out)
    xc, bmat, cmat = jnp.split(conv_out, [di, di + N_GROUPS * st], axis=-1)

    dt = jax.nn.softplus(dt.astype(jnp.float32) +
                         params["dt_bias"].astype(jnp.float32))   # (B, nh)
    a_coef = -jnp.exp(params["A_log"].astype(jnp.float32))        # (nh,)
    xh = xc.reshape(b, nh, cfg.ssm_head_dim).astype(jnp.float32)
    bg = jnp.repeat(bmat.reshape(b, N_GROUPS, st), nh // N_GROUPS,
                    axis=1).astype(jnp.float32)
    cg = jnp.repeat(cmat.reshape(b, N_GROUPS, st), nh // N_GROUPS,
                    axis=1).astype(jnp.float32)

    decay = jnp.exp(dt * a_coef[None])                            # (B, nh)
    h = cache["ssm"] * decay[..., None, None] + jnp.einsum(
        "bhd,bhs->bhds", xh * dt[..., None], bg)
    y = jnp.einsum("bhds,bhs->bhd", h, cg) + \
        xh * params["D"].astype(jnp.float32)[None, :, None]
    new_cache = {"ssm": h,
                 "conv": hist[:, 1:].astype(cache["conv"].dtype)}
    return y.reshape(b, di).astype(cdt), z, new_cache
