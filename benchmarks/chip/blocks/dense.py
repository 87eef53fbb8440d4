"""The program's dense block: a SwiGLU MLP after global SOCKET attention
with full rotary, RMSNorm at eps 1e-6 and an untied head, every layer
alike (one ``LayerSpec`` scanned over the layers).

Operations and bytes of a decode step are what the algorithm needs, not
what the program happens to do: the layer and head weights, and for
each token every layer's SOCKET work (``flops.decode_token``).  Bytes
assume each weight is read once per step and each cache row once per
use.
"""

from __future__ import annotations

import numpy as np

from benchmarks.chip import flops
from benchmarks.chip.model import dims


def program_config(c: dict, *, serving: dict):
    """The program's ``ModelConfig`` for configuration ``c`` and a cell's
    serving geometry: the SOCKET backend on its default (XLA) path, no
    kernel flag set."""
    from repro.configs.base import (LayerSpec, ModelConfig, ServingSettings,
                                    SocketSettings)
    if c["hidden_act"] != "silu" or c.get("sliding_window") is not None \
            or c["tie_word_embeddings"] or c["rms_norm_eps"] != 1e-6:
        raise ValueError(f"{c['name']}: the program's dense block is a "
                         "SwiGLU block over global attention with full "
                         "rotary, RMSNorm at eps 1e-6 and an untied head")
    s = c["socket"]
    dt = c["torch_dtype"]
    return ModelConfig(
        name=c["name"], family="dense", d_model=c["hidden_size"],
        num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
        pattern=(LayerSpec(kind="attn", attn_type="global", mlp="dense"),),
        num_groups=c["num_hidden_layers"], mlp_activation="swiglu",
        qk_norm=dims(c)["qk_norm"], rope_theta=float(c["rope_theta"]),
        param_dtype=dt, compute_dtype=dt, attention_backend="socket",
        socket=SocketSettings(
            num_planes=s["num_planes"], num_tables=s["num_tables"],
            tau=s["tau"], sparsity=s["sparsity"],
            sink_tokens=s["sink_tokens"], window_tokens=s["window_tokens"],
            min_k=s["min_k"], selection=s["selection"]),
        serving=ServingSettings(
            block_size=serving["block_size"],
            num_blocks=serving["pool_blocks"],
            max_batch=serving["max_batch"],
            max_blocks_per_seq=serving["max_blocks_per_seq"],
            prefill_chunk=serving["prefill_chunk"]),
        source=c["source"])


def weight_shapes(c: dict) -> dict:
    """``{path: (shape, dtype, kind)}`` of every leaf, in the program's
    layout: layers stacked on a leading axis for its layer scan."""
    m = dims(c)
    d, ff, h, kv, hd, n, v = (m["d"], m["ff"], m["h"], m["kv"], m["hd"],
                              m["layers"], m["vocab"])
    s = c["socket"]
    wdt = c["torch_dtype"]
    out = {
        # the program scales its lookup by sqrt(d): unit-RMS inputs;
        # its norms scale by (1 + scale): zeros are RMSNorm weights of 1
        "embed/table": ((v, d), wdt, d ** -0.5),
        "embed/head": ((d, v), wdt, d ** -0.5),
        "final_norm/scale": ((d,), "float32", "zeros"),
        "groups/slot_0/norm_mix/scale": ((n, d), "float32", "zeros"),
        "groups/slot_0/norm_mlp/scale": ((n, d), "float32", "zeros"),
        "groups/slot_0/attn/wq": ((n, d, h, hd), wdt, d ** -0.5),
        "groups/slot_0/attn/wk": ((n, d, kv, hd), wdt, d ** -0.5),
        "groups/slot_0/attn/wv": ((n, d, kv, hd), wdt, d ** -0.5),
        "groups/slot_0/attn/wo": ((n, h, hd, d), wdt, (h * hd) ** -0.5),
        "groups/slot_0/attn/hash_w": (
            (n, s["num_tables"], s["num_planes"], hd), "float32", 1.0),
        "groups/slot_0/mlp/w_gate": ((n, d, ff), wdt, d ** -0.5),
        "groups/slot_0/mlp/w_up": ((n, d, ff), wdt, d ** -0.5),
        "groups/slot_0/mlp/w_down": ((n, ff, d), wdt, ff ** -0.5),
    }
    if m["qk_norm"]:
        out["groups/slot_0/attn/q_norm/scale"] = ((n, hd), "float32", "ones")
        out["groups/slot_0/attn/k_norm/scale"] = ((n, hd), "float32", "ones")
    return out


def matmul_params(c: dict) -> int:
    """Parameters that take part in a matrix product for every token:
    the layers' projections and MLP, and the output head (the embedding
    is a lookup; norms and hash planes are not weights of a product)."""
    return sum(int(np.prod(shape)) for path, (shape, _, _)
               in weight_shapes(c).items()
               if path.split("/")[-1] in ("wq", "wk", "wv", "wo", "w_gate",
                                          "w_up", "w_down", "head"))


def layer_weights(weights: dict, i: int) -> dict:
    """Layer ``i``'s leaves, flattened to ``{name: array}``."""
    g = weights["groups"]["slot_0"]
    out = {k: g["attn"][k][i] for k in ("wq", "wk", "wv", "wo", "hash_w")}
    if "q_norm" in g["attn"]:
        out["q_norm"] = g["attn"]["q_norm"]["scale"][i]
        out["k_norm"] = g["attn"]["k_norm"]["scale"][i]
    out["norm_mix"] = g["norm_mix"]["scale"][i]
    out["norm_mlp"] = g["norm_mlp"]["scale"][i]
    out.update({k: g["mlp"][k][i] for k in ("w_gate", "w_up", "w_down")})
    return out


def layer_params(c: dict) -> int:
    m = dims(c)
    d, h, kv, hd, ff = m["d"], m["h"], m["kv"], m["hd"], m["ff"]
    return d * (h + 2 * kv) * hd + h * hd * d + 3 * d * ff


def head_params(c: dict) -> int:
    m = dims(c)
    return m["d"] * m["vocab"]


def weight_flops_per_token(c: dict) -> float:
    """Every layer's products and the head: 2 operations a weight."""
    return 2.0 * (dims(c)["layers"] * layer_params(c) + head_params(c))


def weight_bytes(c: dict, itemsize: int = 2) -> float:
    return itemsize * (dims(c)["layers"] * layer_params(c) + head_params(c))


def step(c: dict, decode_lengths, itemsize: int = 2):
    """(flops, bytes) of one decode step: a token for each request, at
    the given context lengths.  Weights are read once a step; every
    layer attends through SOCKET."""
    layers = dims(c)["layers"]
    wf = weight_flops_per_token(c)
    flops_ = 0.0
    nbytes = weight_bytes(c, itemsize)
    for n in decode_lengths:
        f, b = flops.decode_token(c, n, itemsize)
        flops_ += wf + layers * f
        nbytes += layers * b + dims(c)["d"] * itemsize   # embedding row
    return flops_, nbytes
