"""A configuration file as the program runs it, and its weights.

``load_config(name)`` reads ``configs/<name>.json``; ``block(c)`` is the
module ``blocks/<block>.py`` that its ``block`` key names, which maps it
onto the program's ``ModelConfig`` (the system under test) and gives its
weights' layout and a decode step's work.  ``make_weights`` makes the
weights on the device from the seed in one jitted program, in that
layout.  The plain reference reads the same file and the same weights,
so nothing it uses was made by the program.
"""

from __future__ import annotations

import functools
import importlib.util
import json
from pathlib import Path

import jax
import jax.numpy as jnp

HERE = Path(__file__).resolve().parent


def load_config(name: str, root: Path = HERE) -> dict:
    return json.loads((root / "configs" / f"{name}.json").read_text())


def dims(c: dict) -> dict:
    """The sizes the counters, the weights and the reference share."""
    return {"d": c["hidden_size"], "ff": c["intermediate_size"],
            "h": c["num_attention_heads"], "kv": c["num_key_value_heads"],
            "hd": c["head_dim"], "layers": c["num_hidden_layers"],
            "vocab": c["vocab_size"],
            "qk_norm": bool(c.get("qk_layernorm", False)),
            "theta": float(c["rope_theta"]), "eps": float(c["rms_norm_eps"])}


@functools.cache
def _load_block(path: Path):
    spec = importlib.util.spec_from_file_location(
        f"benchmarks.chip.blocks.{path.stem.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def block(c: dict, root: Path = HERE):
    """The module ``<root>/blocks/<block>.py`` of configuration ``c``
    (``blocks/__init__.py`` states what it provides)."""
    return _load_block(root / "blocks" / f"{c['block']}.py")


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from a seed of any size (``PRNGKey`` alone keeps only
    the low 32 bits)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def _nest(flat: dict) -> dict:
    tree: dict = {"remainder": {}}
    for path, leaf in flat.items():
        node = tree
        *parents, last = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[last] = leaf
    return tree


def make_weights(c: dict, seed: int, root: Path = HERE):
    """All weights on the device, from the seed, in one jitted program:
    every leaf of the block's ``weight_shapes``, in sorted path order."""
    shapes = block(c, root).weight_shapes(c)

    def build(key):
        flat = {}
        for i, (path, (shape, dt, std)) in enumerate(sorted(shapes.items())):
            if std in ("ones", "zeros"):
                flat[path] = getattr(jnp, std)(shape, dt)
            else:
                k = jax.random.fold_in(key, i)
                flat[path] = jax.random.normal(k, shape, dt) * \
                    jnp.asarray(std, dt)
        return _nest(flat)

    return jax.jit(build)(seed_key(seed))
