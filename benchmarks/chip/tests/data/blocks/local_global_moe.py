"""A block that only test data brings: sliding-window and global
attention in turn (``layer_types``), every layer with a top-k MoE MLP.
The harness finds it by the configuration's ``block`` key alone; it
brings no plain reference, so it has no ``layer_weights``.

A decode step reads each dense weight once, and of each layer's experts
those its tokens route to: under uniform routing, ``E (1 - (1 - k/E)^B)``
of ``E`` for ``B`` tokens.  Global layers attend through SOCKET; a
sliding-window layer attends densely over the last ``sliding_window``
keys."""

from __future__ import annotations

from benchmarks.chip import flops
from benchmarks.chip.model import dims

ATTN = {"sliding_attention": "local", "full_attention": "global"}


def pattern(c: dict) -> list:
    """The shortest run of layer types whose repeats make every layer."""
    types, n = c["layer_types"], c["num_hidden_layers"]
    p = next(p for p in range(1, n + 1)
             if n % p == 0 and types == types[:p] * (n // p))
    return [ATTN[t] for t in types[:p]]


def program_config(c: dict, *, serving: dict):
    from repro.configs.base import (LayerSpec, ModelConfig, ServingSettings,
                                    SocketSettings)
    if c["hidden_act"] != "silu" or c["tie_word_embeddings"] \
            or c["rms_norm_eps"] != 1e-6:
        raise ValueError(f"{c['name']}: the program's MoE block is SwiGLU "
                         "experts, RMSNorm at eps 1e-6 and an untied head")
    s, dt, kinds = c["socket"], c["torch_dtype"], pattern(c)
    return ModelConfig(
        name=c["name"], family="moe", d_model=c["hidden_size"],
        num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
        pattern=tuple(LayerSpec(kind="attn", attn_type=k, mlp="moe")
                      for k in kinds),
        num_groups=c["num_hidden_layers"] // len(kinds),
        sliding_window=c["sliding_window"],
        num_experts=c["num_local_experts"],
        num_experts_per_tok=c["num_experts_per_tok"],
        mlp_activation="swiglu", rope_theta=float(c["rope_theta"]),
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
    m = dims(c)
    d, ff, h, kv, hd, v = (m["d"], m["ff"], m["h"], m["kv"], m["hd"],
                           m["vocab"])
    e, s, wdt = c["num_local_experts"], c["socket"], c["torch_dtype"]
    slots = len(pattern(c))
    g = m["layers"] // slots
    out = {"embed/table": ((v, d), wdt, d ** -0.5),
           "embed/head": ((d, v), wdt, d ** -0.5),
           "final_norm/scale": ((d,), "float32", "zeros")}
    for i in range(slots):
        p = f"groups/slot_{i}/"
        out.update({
            p + "norm_mix/scale": ((g, d), "float32", "zeros"),
            p + "norm_mlp/scale": ((g, d), "float32", "zeros"),
            p + "attn/wq": ((g, d, h, hd), wdt, d ** -0.5),
            p + "attn/wk": ((g, d, kv, hd), wdt, d ** -0.5),
            p + "attn/wv": ((g, d, kv, hd), wdt, d ** -0.5),
            p + "attn/wo": ((g, h, hd, d), wdt, (h * hd) ** -0.5),
            p + "attn/hash_w": (
                (g, s["num_tables"], s["num_planes"], hd), "float32", 1.0),
            p + "moe/router": ((g, d, e), "float32", d ** -0.5),
            p + "moe/w_gate": ((g, e, d, ff), wdt, d ** -0.5),
            p + "moe/w_up": ((g, e, d, ff), wdt, d ** -0.5),
            p + "moe/w_down": ((g, e, ff, d), wdt, ff ** -0.5)})
    return out


def matmul_params(c: dict) -> int:
    """Weights a token multiplies by: attention, router, its ``k``
    experts and the head."""
    m = dims(c)
    d, ff, h, kv, hd = m["d"], m["ff"], m["h"], m["kv"], m["hd"]
    per_layer = (d * (h + 2 * kv) * hd + h * hd * d
                 + d * c["num_local_experts"]
                 + c["num_experts_per_tok"] * 3 * d * ff)
    return m["layers"] * per_layer + d * m["vocab"]


def step(c: dict, decode_lengths, itemsize: int = 2):
    m, kinds = dims(c), pattern(c)
    d, ff, kv, hd, h = m["d"], m["ff"], m["kv"], m["hd"], m["h"]
    e, k = c["num_local_experts"], c["num_experts_per_tok"]
    per_kind = m["layers"] // len(kinds)
    window = c["sliding_window"]
    b = len(decode_lengths)
    experts_read = e * (1 - (1 - k / e) ** b)
    dense_params = matmul_params(c) - m["layers"] * k * 3 * d * ff
    nbytes = itemsize * (dense_params
                         + m["layers"] * experts_read * 3 * d * ff)
    flops_ = 0.0
    for n in decode_lengths:
        flops_ += 2.0 * matmul_params(c)
        nbytes += d * itemsize                           # embedding row
        for kind in kinds:
            if kind == "global":
                f, by = flops.decode_token(c, n, itemsize)
            else:
                w = min(n, window)
                f, by = 4 * h * w * hd, w * kv * 2 * hd * itemsize
            flops_ += per_kind * f
            nbytes += per_kind * by
    return flops_, nbytes
