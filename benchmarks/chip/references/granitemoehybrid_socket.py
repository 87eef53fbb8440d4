"""Plain float32 reference: granite-4.0-h's hybrid decoder (HF
transformers' ``granitemoehybrid``), whose attention layers decode
through SOCKET, as a configuration file states it.

Written from the configuration and the model's layer equations, in
straightforward ``jax.numpy`` at HIGHEST matmul precision, one layer at
a time, with no cache, paging, batching or chunked scan:

* ``x0 = embedding_multiplier * embed(t)``;
* ``h = x + residual_multiplier * mixer(rmsnorm(x))``, the mixer Mamba-2
  or GQA attention with no position embedding whose logits are scaled by
  ``attention_multiplier``;
* ``x' = h + residual_multiplier * (moe(rmsnorm(h)) + shared(rmsnorm(h)))``:
  the router takes the top ``k`` of its logits and a softmax over those
  ``k``; of the experts, the held ones (``num_local_experts`` from
  ``first_held_expert``, one chip's share of the router's published
  width) add their weighted SwiGLU output; the shared SwiGLU expert
  takes every token;
* ``logits = rmsnorm(x_L) @ embed^T / logits_scaling``.

Mamba-2: ``in_proj`` to ``[z | x | B | C | dt]``; a causal depthwise
conv with bias and SiLU over ``(x, B, C)``; ``dt = softplus(dt +
dt_bias)``; with ``A = -exp(A_log)``, position by position
(``lax.scan``), ``h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T`` and
``y_t = C_t h_t + D x_t``; the RMSNorm of ``y * silu(z)`` over all inner
channels, times its weight; ``out_proj``.

Prompt positions attend densely and causally; every position past the
prompt attends over the keys SOCKET selects for it, by
``dense_socket``'s SOCKET helpers (Algorithms 1-3).  ``mode`` names the
control, as there.  Positions are computed in blocks, and the Mamba and
MoE layers stop at the last position served, so that 31k-token prompts
fit and lengths share compiled programs.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip.references.dense_socket import (CONTROL, _dense_ctx,
                                                     _mm, _rmsnorm,
                                                     _socket_ctx)

F32 = jnp.float32


def _pow2(n: int, floor: int) -> int:
    return max(floor, 1 << (int(n) - 1).bit_length())


def _blocks(fn, x, block):
    """A ``fori_loop`` body running ``fn(carry, x_block) -> (carry,
    y_block)`` on block ``i`` of x (T, d) and writing it into y."""
    def body(i, state):
        carry, y = state
        xb = jax.lax.dynamic_slice_in_dim(x, i * block, block)
        carry, yb = fn(carry, xb)
        return carry, jax.lax.dynamic_update_slice_in_dim(y, yb, i * block,
                                                          0)
    return body


@functools.partial(jax.jit, static_argnames=("md", "eps", "res", "mode",
                                             "block"))
def _mamba(lw, x, n, *, md, eps, res, mode, block):
    """x + res * Mamba-2(rmsnorm(x)) at positions below ``n``."""
    md = dict(md)
    di, nh, hd, st, k, cw = (md["di"], md["nh"], md["hd"], md["st"],
                             md["width"], md["conv"])
    a = -jnp.exp(lw["A_log"].astype(F32))
    w = lw["conv_w"].astype(F32)                             # (conv, K)

    def position(h, inp):
        x_t, dt_t, b_t, c_t = inp
        h = h * jnp.exp(dt_t * a)[:, None, None] + \
            (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :]
        y = _mm("hds,s->hd", h, c_t, mode) + \
            lw["D"].astype(F32)[:, None] * x_t
        return h, y

    def segment(carry, xb):
        h, tail = carry
        proj = _mm("td,de->te", _rmsnorm(xb, lw["norm_mix"], eps),
                   lw["in_proj"], mode)
        z, xbc, dt = proj[:, :di], proj[:, di:di + cw], proj[:, di + cw:]
        u = jnp.concatenate([tail, xbc], axis=0)
        conv = sum(u[i:i + block] * w[:, i] for i in range(k))
        conv = jax.nn.silu(conv + lw["conv_b"].astype(F32))
        dt = jax.nn.softplus(dt + lw["dt_bias"].astype(F32))
        h, y = jax.lax.scan(position, h, (
            conv[:, :di].reshape(block, nh, hd), dt,
            conv[:, di:di + st], conv[:, di + st:]))
        y = y.reshape(block, di) * jax.nn.silu(z)
        y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + eps)
        y = y * lw["norm_scale"].astype(F32)
        return (h, u[block:]), _mm("te,ed->td", y, lw["out_proj"], mode)

    carry = (jnp.zeros((nh, hd, st), F32), jnp.zeros((k - 1, cw), F32))
    _, y = jax.lax.fori_loop(0, -(-n // block), _blocks(segment, x, block),
                             (carry, jnp.zeros_like(x)))
    return x + res * y


@functools.partial(jax.jit, static_argnames=("moe", "eps", "res", "mode",
                                             "block"))
def _moe(lw, x, n, *, moe, eps, res, mode, block):
    """x + res * (held experts' part + shared expert)(rmsnorm(x)) at
    positions below ``n``."""
    moe = dict(moe)
    first, held, k = moe["first"], moe["held"], moe["k"]

    def segment(carry, xb):
        hm = _rmsnorm(xb, lw["norm_mlp"], eps)
        logits = _mm("td,de->te", hm, lw["router"], mode)
        top, idx = jax.lax.top_k(logits, k)
        gates = jnp.zeros_like(logits).at[
            jnp.arange(block)[:, None], idx].set(jax.nn.softmax(top, axis=-1))
        gates = gates[:, first:first + held]                 # (t, held)
        gate = _mm("td,edf->tef", hm, lw["w_gate"], mode)
        up = _mm("td,edf->tef", hm, lw["w_up"], mode)
        act = jax.nn.silu(gate) * up * gates[:, :, None]
        y = _mm("tef,efd->td", act, lw["w_down"], mode)
        sg = _mm("td,df->tf", hm, lw["shared_w_gate"], mode)
        su = _mm("td,df->tf", hm, lw["shared_w_up"], mode)
        y = y + _mm("tf,fd->td", jax.nn.silu(sg) * su, lw["shared_w_down"],
                    mode)
        return carry, y

    _, y = jax.lax.fori_loop(0, -(-n // block), _blocks(segment, x, block),
                             (0, jnp.zeros_like(x)))
    return x + res * y


@functools.partial(jax.jit, static_argnames=("dm", "sock_items", "mode",
                                             "qblock", "sblock"))
def _attention(lw, x, prompt_len, *, dm, sock_items, mode, qblock, sblock):
    """x + res * attention(rmsnorm(x)): dense and causal at prompt
    positions, through SOCKET at the ``dec`` positions from the prompt's
    end."""
    dm = dict(dm)
    sock = dict(sock_items)
    hn = _rmsnorm(x, lw["norm_mix"], dm["eps"])
    q = _mm("td,dhk->thk", hn, lw["wq"], mode)
    k = _mm("td,dhk->thk", hn, lw["wk"], mode)
    v = _mm("td,dhk->thk", hn, lw["wv"], mode)
    ctx = _dense_ctx(q, k, v, dm["kv"], dm["scale"], mode, qblock)
    qd = jax.lax.dynamic_slice_in_dim(q, prompt_len, dm["dec"], axis=0)
    cd = _socket_ctx(qd, prompt_len + jnp.arange(dm["dec"]), k, v,
                     lw["hash_w"].astype(F32), sock, dm["kv"], dm["scale"],
                     mode, sblock)
    ctx = jax.lax.dynamic_update_slice_in_dim(ctx, cd, prompt_len, axis=0)
    return x + dm["res"] * _mm("thk,hkd->td", ctx, lw["wo"], mode)


@functools.partial(jax.jit, static_argnames=("eps", "div", "mode"))
def _head(xs, norm, table, *, eps, div, mode):
    return _mm("sd,vd->sv", _rmsnorm(xs, norm, eps), table, mode) / div


def served_logits(cfg: dict, weights, prompt, served, *, mode="f32",
                  block: int = 512, qblock: int = 64, sblock: int = 2,
                  head_blocks: int = 8):
    """Logits ``(len(served), vocab)`` at each position that produced a
    served token: the last prompt position, then each decode step, with
    the served tokens fed back (teacher-forced)."""
    from benchmarks.chip.blocks.granitemoehybrid import (layer_kinds,
                                                         layer_weights,
                                                         mamba_dims)
    from benchmarks.chip.model import dims
    dm = dims(cfg)
    prompt = np.asarray(prompt, np.int32)
    served = np.asarray(served, np.int32)
    p, s = len(prompt), len(served)
    # lengths in powers of two, so that a run's sessions share programs
    dec = _pow2(s, 64)
    t = _pow2(p + dec, 128)
    toks = np.zeros(t, np.int32)
    toks[:p] = prompt
    toks[p:p + s - 1] = served[:-1]
    table = weights["embed"]["table"]
    x = jnp.take(table, jnp.asarray(toks), axis=0).astype(F32) * \
        float(cfg["embedding_multiplier"])
    res, eps = float(cfg["residual_multiplier"]), dm["eps"]
    n = jnp.int32(p + s)
    blk = min(block, t)
    md = tuple(sorted(mamba_dims(cfg).items()))
    moe = (("first", cfg.get("first_held_expert", 0)),
           ("held", cfg["num_local_experts"]),
           ("k", cfg["num_experts_per_tok"]))
    att = tuple(sorted({"kv": dm["kv"], "eps": eps, "res": res,
                        "scale": float(cfg["attention_multiplier"]),
                        "dec": dec}.items()))
    sock = tuple(sorted(cfg["socket"].items()))
    for i, kind in enumerate(layer_kinds(cfg)):
        lw = layer_weights(weights, i)
        if kind == "mamba":
            x = _mamba(lw, x, n, md=md, eps=eps, res=res, mode=mode,
                       block=blk)
        else:
            x = _attention(lw, x, jnp.int32(p), dm=att, sock_items=sock,
                           mode=mode, qblock=min(qblock, t), sblock=sblock)
        x = _moe(lw, x, n, moe=moe, eps=eps, res=res, mode=mode, block=blk)
    xs = jax.lax.dynamic_slice_in_dim(x, p - 1, dec, axis=0)
    v = table.shape[0]
    rows = v // head_blocks
    parts = [_head(xs, weights["final_norm"]["scale"],
                   table[j * rows:(j + 1) * rows], eps=eps,
                   div=float(cfg["logits_scaling"]), mode=mode)
             for j in range(head_blocks)]
    return jnp.concatenate(parts, axis=1)[:s, :dm["vocab"]]


def served_gaps(cfg: dict, weights, prompt, served) -> np.ndarray:
    """Per served token: how far its reference logit lies below the
    reference's best at that position (0 where it is the argmax)."""
    lg = served_logits(cfg, weights, prompt, served)
    tok = jnp.asarray(np.asarray(served, np.int32))
    best = jnp.max(lg, axis=-1)
    got = jnp.take_along_axis(lg, tok[:, None], axis=-1)[:, 0]
    return np.asarray(best - got, np.float64)


def control_gaps(cfg: dict, weights, prompt, served) -> np.ndarray:
    """The control: at each of the same positions, the gap of the token
    that the reference one precision below the configuration's ranks
    first."""
    ref = served_logits(cfg, weights, prompt, served)
    low = served_logits(cfg, weights, prompt, served,
                        mode=CONTROL[cfg["torch_dtype"]])
    pick = jnp.argmax(low, axis=-1)
    best = jnp.max(ref, axis=-1)
    got = jnp.take_along_axis(ref, pick[:, None], axis=-1)[:, 0]
    return np.asarray(best - got, np.float64)
