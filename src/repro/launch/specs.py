"""Input ShapeDtypeStruct specs and sharding assembly for every
(architecture × input-shape × mesh) dry-run cell.

Nothing here allocates device memory: parameters, optimizer states and
caches are built with ``jax.eval_shape`` over the real init functions, so
the dry-run lowers exactly the production pytrees.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from repro.configs.base import ModelConfig
from repro.distributed import sharding as shd
from repro.models import param as pm
from repro.models import transformer as tfm
from repro.optim import AdamWConfig, init_adamw

__all__ = ["ShapeSpec", "SHAPES", "dryrun_model_config", "arch_rules",
           "batch_specs", "param_specs", "opt_specs", "cache_specs",
           "scalar_sharding", "input_specs"]


@dataclasses.dataclass(frozen=True)
class ShapeSpec:
    name: str
    kind: str            # "train" | "prefill" | "decode"
    seq_len: int
    global_batch: int
    long_context: bool = False


SHAPES: Dict[str, ShapeSpec] = {
    "train_4k": ShapeSpec("train_4k", "train", 4096, 256),
    "prefill_32k": ShapeSpec("prefill_32k", "prefill", 32768, 32),
    "decode_32k": ShapeSpec("decode_32k", "decode", 32768, 128),
    "long_500k": ShapeSpec("long_500k", "decode", 524288, 1,
                           long_context=True),
}


def dryrun_model_config(cfg: ModelConfig, shape: ShapeSpec) -> ModelConfig:
    """Deployment numerics + memory policies for the production lowering."""
    sock = dataclasses.replace(cfg.socket, score_chunk=16384,
                               score_dtype="bfloat16")
    out = cfg.replace(
        param_dtype="bfloat16",
        compute_dtype="bfloat16",
        remat_policy="full" if shape.kind == "train" else "none",
        attn_q_chunk=1024 if shape.seq_len > 4096 else 0,
        socket=sock,
    )
    return out


def arch_rules(cfg: ModelConfig, shape: ShapeSpec, mesh: Mesh) -> Dict:
    """Per-(arch, shape) overrides of the logical sharding rules."""
    rules: Dict[str, Any] = {}
    model_size = mesh.shape.get("model", 1)
    kv_div = cfg.num_kv_heads and cfg.num_kv_heads % model_size == 0
    if shape.kind in ("decode", "prefill"):
        if shape.long_context:
            # context parallelism: cache sequence over the data axis (plus
            # model when KV heads cannot use it — e.g. kv=8 on 16-way TP)
            rules["cache_seq_cp"] = ("data", "model") if not kv_div \
                else ("pod", "data")
            rules["cache_heads"] = ("model",) if kv_div else None
            # batch=1: activations replicated over data
            rules["batch"] = None
            rules["cache_batch"] = None
        elif not kv_div and cfg.num_kv_heads:
            # kv heads unshardable: spread the cache over sequence instead
            rules["cache_seq"] = ("model",)
            rules["cache_heads"] = None
    # q8 optimizer-state flats
    rules["q8_flat"] = ("pod", "data", "model")
    rules["q8_scale"] = ("data", "model")
    return rules


def _named(mesh: Mesh, axes, shape, rules, log) -> NamedSharding:
    return shd.named_sharding(mesh, axes, shape, rules, log)


def scalar_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, PartitionSpec())


# --------------------------------------------------------------- parameters

def param_specs(cfg: ModelConfig, mesh: Mesh, rules: Dict,
                log: Optional[List[str]] = None):
    """(values_sds, shardings) for the model parameters via eval_shape."""
    boxed = jax.eval_shape(
        functools.partial(tfm.init_model, cfg), jax.random.PRNGKey(0))
    values = pm.unbox(boxed)
    axes = pm.axes_of(boxed)
    flat_v, treedef = jax.tree_util.tree_flatten(values)
    flat_a = jax.tree_util.tree_leaves(
        axes, is_leaf=lambda x: isinstance(x, tuple))
    shardings = [
        _named(mesh, a, v.shape, rules, log) for v, a in zip(flat_v, flat_a)]
    return values, jax.tree_util.tree_unflatten(treedef, shardings)


# ---------------------------------------------------------- optimizer state

def opt_specs(ocfg: AdamWConfig, params_sds, param_shardings, mesh: Mesh,
              rules: Dict, log: Optional[List[str]] = None):
    """(opt_sds, opt_shardings); moments inherit parameter shardings
    (ZeRO-over-FSDP), int8 states shard their flat axes."""
    opt_sds = jax.eval_shape(
        functools.partial(init_adamw, ocfg), params_sds)

    def is_q8(x):
        return isinstance(x, dict) and set(x) == {"q", "scale"}

    def _fit(spec: PartitionSpec, shape) -> NamedSharding:
        """Reuse a param spec on a congruent-rank tensor, dropping entries
        that no longer divide (e.g. the blocked scale's last dim)."""
        entries = tuple(spec) + (None,) * (len(shape) - len(tuple(spec)))
        out = []
        for dim, e in enumerate(entries):
            if e is None:
                out.append(None)
                continue
            axes = (e,) if isinstance(e, str) else tuple(e)
            size = int(np.prod([mesh.shape[a] for a in axes]))
            out.append(e if shape[dim] % size == 0 else None)
        return NamedSharding(mesh, PartitionSpec(*out))

    def moment_shardings(tree):
        flat_m, tdef = jax.tree_util.tree_flatten(tree, is_leaf=is_q8)
        flat_p = jax.tree_util.tree_leaves(param_shardings)
        out = []
        for m, psh in zip(flat_m, flat_p):
            if is_q8(m):
                # q keeps the parameter's sharding (same rank, last dim
                # padded); scale drops the last-dim entry
                pspec = tuple(psh.spec)
                scale_spec = PartitionSpec(
                    *(pspec[:len(m["scale"].shape) - 1] +
                      ((None,) if len(m["scale"].shape) else ())))
                out.append({
                    "q": _fit(psh.spec, m["q"].shape),
                    "scale": _fit(scale_spec, m["scale"].shape),
                })
            elif getattr(m, "shape", None) == ():
                out.append(scalar_sharding(mesh))
            else:
                out.append(psh)
        return jax.tree_util.tree_unflatten(tdef, out)

    opt_sh = {
        "step": scalar_sharding(mesh),
        "m": moment_shardings(opt_sds["m"]),
        "v": moment_shardings(opt_sds["v"]),
    }
    return opt_sds, opt_sh


# ------------------------------------------------------------------- batch

def batch_specs(cfg: ModelConfig, shape: ShapeSpec, mesh: Mesh, rules: Dict,
                log: Optional[List[str]] = None):
    """(batch_sds, batch_shardings) for train/prefill inputs."""
    b, s = shape.global_batch, shape.seq_len
    sds: Dict[str, jax.ShapeDtypeStruct] = {}
    axes: Dict[str, tuple] = {}
    if cfg.input_mode == "tokens":
        sds["tokens"] = jax.ShapeDtypeStruct((b, s), jnp.int32)
        axes["tokens"] = ("batch", "seq")
    else:
        sds["embeds"] = jax.ShapeDtypeStruct((b, s, cfg.d_model),
                                             jnp.bfloat16)
        axes["embeds"] = ("batch", "seq", "embed")
    if shape.kind == "train":
        sds["labels"] = jax.ShapeDtypeStruct((b, s), jnp.int32)
        axes["labels"] = ("batch", "seq")
    sh = {k: _named(mesh, axes[k], sds[k].shape, rules, log) for k in sds}
    return sds, sh


def decode_input_specs(cfg: ModelConfig, shape: ShapeSpec, mesh: Mesh,
                       rules: Dict, log=None):
    b = shape.global_batch
    if cfg.input_mode == "tokens":
        sds = jax.ShapeDtypeStruct((b, 1), jnp.int32)
        sh = _named(mesh, ("batch", None), sds.shape, rules, log)
    else:
        sds = jax.ShapeDtypeStruct((b, 1, cfg.d_model), jnp.bfloat16)
        sh = _named(mesh, ("batch", None, "embed"), sds.shape, rules, log)
    return sds, sh


# ------------------------------------------------------------------- cache

def cache_specs(cfg: ModelConfig, shape: ShapeSpec, mesh: Mesh, rules: Dict,
                log: Optional[List[str]] = None):
    """(cache_sds, cache_shardings) for the decode caches."""
    sds = jax.eval_shape(functools.partial(
        tfm.init_decode_caches, cfg, shape.global_batch, shape.seq_len,
        shape.long_context))
    axes = tfm.decode_cache_axes(cfg, shape.long_context)
    flat_s, treedef = jax.tree_util.tree_flatten(sds)
    flat_a = jax.tree_util.tree_leaves(
        axes, is_leaf=lambda x: isinstance(x, tuple))
    if len(flat_s) != len(flat_a):
        raise ValueError(
            f"cache sds/axes mismatch: {len(flat_s)} vs {len(flat_a)}")
    sh = [
        _named(mesh, a, v.shape, rules, log) for v, a in zip(flat_s, flat_a)]
    return sds, jax.tree_util.tree_unflatten(treedef, sh)


def input_specs(arch: str, shape_name: str = "train_4k"):
    """ShapeDtypeStruct stand-ins for every model input of one cell —
    weak-type-correct, shardable, no device allocation.

    For a training step: {"tokens"|"embeds": ..., "labels": ...};
    for prefill: the prompt batch; for decode: the full
    (params, caches, inp, pos) keyword set matching
    ``runtime.steps.make_serve_step``.

        lowered = jax.jit(train_step).lower(params, opt, **input_specs(a))
    """
    import jax as _jax
    from repro.configs import get_config

    shape = SHAPES[shape_name]
    # AbstractMesh: the production 16x16 topology without touching device
    # state (usable for divisibility-checked spec construction anywhere)
    mesh = _jax.sharding.AbstractMesh((16, 16), ("data", "model"))
    cfg = dryrun_model_config(get_config(arch), shape)
    rules = arch_rules(cfg, shape, mesh)
    if shape.kind in ("train", "prefill"):
        sds, _ = batch_specs(cfg, shape, mesh, rules)
        if shape.kind == "prefill":
            sds.pop("labels", None)
        return {"batch": sds}
    cache_sds, _ = cache_specs(cfg, shape, mesh, rules)
    inp_sds, _ = decode_input_specs(cfg, shape, mesh, rules)
    return {"caches": cache_sds, "inp": inp_sds,
            "pos": _jax.ShapeDtypeStruct((), jnp.int32)}
