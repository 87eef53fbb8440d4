"""Plain float32 reference: a dense GQA decoder whose decode steps use
SOCKET sparse attention, as a configuration file states it.

Written from the configuration and the SOCKET paper (Algorithms 1-3),
in straightforward ``jax.numpy`` at HIGHEST matmul precision, one layer
at a time, with no cache, paging or batching.  Prompt positions attend
densely and causally (the engine prefills densely); every position past
the prompt attends over the keys SOCKET selects for it:

* each key is hashed by ``L`` tables of ``P`` Gaussian planes to signs
  ``S_j`` (Algorithm 1), and its value norm ``||v_j||`` is kept;
* the query of each head is soft-hashed, ``u = tanh(W q) / sqrt(hd)``
  (Algorithm 2), and scores key ``j`` with
  ``sum_l exp(S_j . u_l / tau - log Z_l)``, ``Z_l = prod cosh``-sum
  over the ``2^P`` corners; the scores of a KV head's query group add up;
* the ``k = max(ceil(n / sparsity), min(n, sink + window), min_k)`` keys
  of largest ``score * ||v||`` are kept, the first ``sink`` and last
  ``window`` always (Algorithm 3, lowest index first among ties), and
  the heads attend exactly over them.  Value norms are rounded to
  bfloat16, the precision SOCKET's side cache keeps them in.

``mode`` names the control, the reference in the precision below the
one the configuration states: ``"fp8"`` (below bfloat16) rounds both
operands of every matrix product to float8 e4m3 with per-tensor absmax
scaling; ``"bf16"`` (below float32) rounds them to bfloat16.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
NEG = -1e30
F8_MAX = 448.0


CONTROL = {"bfloat16": "fp8", "float32": "bf16"}


def _round(x, mode):
    if mode == "f32":
        return x
    if mode == "bf16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(eq, a, b, mode):
    return jnp.einsum(eq, _round(a.astype(jnp.float32), mode),
                      _round(b.astype(jnp.float32), mode), precision=HIGHEST)


def _rmsnorm(x, scale, eps):
    x = x.astype(jnp.float32)
    y = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return y * (1.0 + scale.astype(jnp.float32))


def _rope(x, pos, theta):
    """x (T, heads, hd), pos (T,): rotate pairs (i, i + hd/2)."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None, None] * freq
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def budget(sock: dict, n, xp=jnp):
    """Keys attended at context length ``n`` (Algorithm 3's k with the
    forced sink + window floor); ``xp=np`` for a static length."""
    forced = xp.minimum(n, sock["sink_tokens"] + sock["window_tokens"])
    k = xp.ceil(n / sock["sparsity"]).astype(xp.int32)
    return xp.minimum(xp.maximum(xp.maximum(k, forced), sock["min_k"]), n)


def _dense_ctx(q, k, v, kv, scale, mode, block):
    """Causal attention of every position; q (T, H, hd), k/v (T, KV, hd)."""
    t, h, hd = q.shape
    g = h // kv
    qb = q.reshape(t // block, block, kv, g, hd)

    def one(args):
        qc, i0 = args
        lg = _mm("qkgd,skd->kgqs", qc, k, mode) * scale
        ti = i0 + jnp.arange(block)[:, None]
        lg = jnp.where(jnp.arange(t)[None, :] <= ti, lg, NEG)
        w = jax.nn.softmax(lg, axis=-1)
        return _mm("kgqs,skd->qkgd", w, v, mode)

    out = jax.lax.map(one, (qb, jnp.arange(t // block) * block))
    return out.reshape(t, h, hd)


def _socket_ctx(qd, pos, k, v, hash_w, sock, kv, scale, mode, block):
    """SOCKET attention of decode queries qd (D, H, hd) at positions
    ``pos`` (D,) over keys k/v (T, KV, hd)."""
    d, h, hd = qd.shape
    t = k.shape[0]
    g = h // kv
    lt, lp = sock["num_tables"], sock["num_planes"]
    tau = sock["tau"]
    signs = jnp.where(_mm("skd,lpd->kslp", k, hash_w, mode) >= 0.0, 1.0,
                      -1.0)                                 # (KV,T,L,P)
    # value norms are kept in bfloat16, as SOCKET's side cache stores them
    vnorm = jnp.sqrt(jnp.sum(v * v, axis=-1)).T.astype(jnp.bfloat16) \
        .astype(jnp.float32)                                # (KV,T)
    kmax = int(budget(sock, np.int32(t), xp=np))
    qb = qd.reshape(d // block, block, kv, g, hd)
    pb = pos.reshape(d // block, block)

    def one(args):
        qc, pc = args                                       # (b,KV,G,hd)
        u = jnp.tanh(_mm("qkgd,lpd->qkglp", qc, hash_w, mode)) / \
            math.sqrt(hd)
        x = u / tau
        logz = jnp.sum(jnp.abs(x) + jnp.log1p(jnp.exp(-2.0 * jnp.abs(x))),
                       axis=-1)                             # (b,KV,G,L)
        dots = _mm("kslp,qkglp->qkgsl", signs, u, mode) / tau
        score = jnp.sum(jnp.exp(dots - logz[:, :, :, None, :]),
                        axis=(2, 4))                        # (b,KV,T)
        n = pc + 1
        j = jnp.arange(t)[None, None, :]
        eff = score * vnorm[None]
        forced = (j < sock["sink_tokens"]) | \
            (j >= n[:, None, None] - sock["window_tokens"])
        eff = jnp.where(forced, jnp.finfo(jnp.float32).max, eff)
        eff = jnp.where(j < n[:, None, None], eff, NEG)
        _, idx = jax.lax.top_k(eff, kmax)                   # (b,KV,kmax)
        keep = jnp.arange(kmax)[None, None, :] < \
            budget(sock, n)[:, None, None]
        sel = jnp.zeros(eff.shape, bool)
        bi = jnp.arange(block)[:, None, None]
        hi = jnp.arange(kv)[None, :, None]
        sel = sel.at[bi, hi, idx].set(keep)
        lg = _mm("qkgd,skd->qkgs", qc, k, mode) * scale
        lg = jnp.where(sel[:, :, None, :], lg, NEG)
        w = jax.nn.softmax(lg, axis=-1)
        return _mm("qkgs,skd->qkgd", w, v, mode)

    out = jax.lax.map(one, (qb, pb))
    return out.reshape(d, h, hd)


@functools.partial(jax.jit, static_argnames=("dm", "sock_items", "mode",
                                             "qblock", "sblock"))
def _layer(lw, x, prompt_len, *, dm, sock_items, mode, qblock, sblock):
    """One block over the whole padded sequence x (T, d); positions at or
    past ``prompt_len`` attend through SOCKET."""
    dm = dict(dm)
    sock = dict(sock_items)
    t = x.shape[0]
    h, kv, hd = dm["h"], dm["kv"], dm["hd"]
    pos = jnp.arange(t)
    eps = dm["eps"]
    hn = _rmsnorm(x, lw["norm_mix"], eps)
    q = _mm("td,dhk->thk", hn, lw["wq"], mode)
    k = _mm("td,dhk->thk", hn, lw["wk"], mode)
    v = _mm("td,dhk->thk", hn, lw["wv"], mode)
    if dm["qk_norm"]:
        q = _rmsnorm(q, lw["q_norm"], eps)
        k = _rmsnorm(k, lw["k_norm"], eps)
    q = _rope(q, pos, dm["theta"])
    k = _rope(k, pos, dm["theta"])
    scale = 1.0 / math.sqrt(hd)
    ctx = _dense_ctx(q, k, v, kv, scale, mode, qblock)
    qd = jax.lax.dynamic_slice_in_dim(q, prompt_len, dm["dec"], axis=0)
    cd = _socket_ctx(qd, prompt_len + jnp.arange(dm["dec"]), k, v,
                     lw["hash_w"].astype(jnp.float32), sock, kv, scale,
                     mode, sblock)
    ctx = jax.lax.dynamic_update_slice_in_dim(ctx, cd, prompt_len, axis=0)
    x = x + _mm("thk,hkd->td", ctx, lw["wo"], mode)
    hm = _rmsnorm(x, lw["norm_mlp"], eps)
    gate = _mm("td,df->tf", hm, lw["w_gate"], mode)
    up = _mm("td,df->tf", hm, lw["w_up"], mode)
    return x + _mm("tf,fd->td", jax.nn.silu(gate) * up, lw["w_down"], mode)


@functools.partial(jax.jit, static_argnames=("eps", "mode"))
def _head(xs, norm, head, *, eps, mode):
    return _mm("sd,dv->sv", _rmsnorm(xs, norm, eps), head, mode)


def _pad_to(n, m):
    return -(-n // m) * m


def served_logits(cfg: dict, weights, prompt, served, *, mode="f32",
                  bucket: int = 1024, qblock: int = 256, sblock: int = 4,
                  head_blocks: int = 8):
    """Logits ``(len(served), vocab)`` at each position that produced a
    served token: the last prompt position, then each decode step, with
    the served tokens fed back (teacher-forced)."""
    from benchmarks.chip.blocks.dense import layer_weights
    from benchmarks.chip.model import dims
    dm = dims(cfg)
    prompt = np.asarray(prompt, np.int32)
    served = np.asarray(served, np.int32)
    p, s = len(prompt), len(served)
    dec = _pad_to(s + 8, 64)
    t = _pad_to(p + dec, bucket)
    toks = np.zeros(t, np.int32)
    toks[:p] = prompt
    toks[p:p + s - 1] = served[:-1]
    x = jnp.take(weights["embed"]["table"], jnp.asarray(toks), axis=0)
    x = x.astype(jnp.float32) * math.sqrt(dm["d"])
    dmi = tuple(sorted({**dm, "dec": dec}.items()))
    sock = tuple(sorted(cfg["socket"].items()))
    for i in range(dm["layers"]):
        x = _layer(layer_weights(weights, i), x, jnp.int32(p), dm=dmi,
                   sock_items=sock, mode=mode, qblock=qblock, sblock=sblock)
    xs = x[p - 1:p - 1 + _pad_to(s, 8)]
    head = weights["embed"]["head"]
    v = head.shape[1]
    cols = v // head_blocks
    parts = [_head(xs, weights["final_norm"]["scale"],
                   head[:, j * cols:(j + 1) * cols], eps=dm["eps"],
                   mode=mode)
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
