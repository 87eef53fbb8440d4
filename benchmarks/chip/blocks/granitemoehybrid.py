"""The program's granite-4.0-h hybrid block: Mamba-2 mixers and NoPE GQA
attention in the order ``layer_types`` gives, every layer with an MoE
MLP (top-k routing over the router's published width, of which this
chip holds a share of the experts) plus a shared SwiGLU expert, and the
four multipliers of the embedding, the attention logits, both residual
branches and the output logits.  RMSNorm at eps 1e-6 and a head tied to
the embedding.

Operations and bytes of a decode step are what the algorithm needs:
every dense weight is read once a step (the embedding once, as the
head); of the held experts, those the step's tokens route to under
uniform routing, ``H (1 - (1 - k/E)^B)`` of ``H`` held for ``B`` tokens
over a router of ``E``; each Mamba layer reads and writes each request's
state (the SSD state in float32 and the conv tail); the attention layers
do SOCKET's work (``flops.decode_token``).  A token multiplies by the
``k H / E`` held experts it routes to here on average.
"""

from __future__ import annotations

import numpy as np

from benchmarks.chip import flops
from benchmarks.chip.model import dims

KIND = {"mamba": "mamba", "attention": "attn"}


def layer_kinds(c: dict) -> list:
    """``"mamba"`` or ``"attn"`` for each layer."""
    return [KIND[t] for t in c["layer_types"][:c["num_hidden_layers"]]]


def pattern(c: dict) -> list:
    """The shortest run of layer kinds whose repeats make every layer."""
    kinds = layer_kinds(c)
    n = len(kinds)
    p = next(p for p in range(1, n + 1)
             if n % p == 0 and kinds == kinds[:p] * (n // p))
    return kinds[:p]


def router_width(c: dict) -> int:
    """Experts the router scores: the published count where this chip
    holds a share of them."""
    return c.get("source_values", {}).get("num_local_experts",
                                          c["num_local_experts"])


def mamba_dims(c: dict) -> dict:
    di = c["mamba_expand"] * c["hidden_size"]
    st, nh = c["mamba_d_state"], c["mamba_n_heads"]
    return {"di": di, "nh": nh, "hd": c["mamba_d_head"], "st": st,
            "conv": di + 2 * c["mamba_n_groups"] * st,
            "width": c["mamba_d_conv"],
            "proj": 2 * di + 2 * c["mamba_n_groups"] * st + nh}


def program_config(c: dict, *, serving: dict):
    """The program's ``ModelConfig`` for configuration ``c`` and a cell's
    serving geometry: SOCKET on the attention layers on its default
    path, no kernel flag set."""
    from repro.configs.base import (LayerSpec, ModelConfig, ServingSettings,
                                    SocketSettings)
    md = mamba_dims(c)
    if c["hidden_act"] != "silu" or not c["tie_word_embeddings"] \
            or c["rms_norm_eps"] != 1e-6 \
            or c["position_embedding_type"] != "nope" \
            or c["mamba_n_groups"] != 1 or not c["mamba_conv_bias"] \
            or c["mamba_proj_bias"] or c["attention_bias"] \
            or md["nh"] * md["hd"] != md["di"]:
        raise ValueError(f"{c['name']}: the program's granitemoehybrid "
                         "block is SwiGLU experts, NoPE attention without "
                         "bias, Mamba-2 with one group, a conv bias and no "
                         "projection bias, RMSNorm at eps 1e-6 and a tied "
                         "head")
    s, dt, kinds = c["socket"], c["torch_dtype"], pattern(c)
    return ModelConfig(
        name=c["name"], family="hybrid", d_model=c["hidden_size"],
        num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        vocab_size=c["vocab_size"],
        pattern=tuple(LayerSpec(kind=k, attn_type="global", mlp="moe")
                      for k in kinds),
        num_groups=c["num_hidden_layers"] // len(kinds),
        num_experts=router_width(c),
        num_experts_per_tok=c["num_experts_per_tok"],
        expert_d_ff=c["intermediate_size"],
        shared_expert_d_ff=c["shared_intermediate_size"],
        first_held_expert=c.get("first_held_expert", 0),
        num_held_experts=c["num_local_experts"],
        ssm_state=md["st"], ssm_expand=c["mamba_expand"],
        ssm_head_dim=md["hd"], ssm_conv_width=md["width"],
        ssm_chunk=c["mamba_chunk_size"], mlp_activation="swiglu",
        use_rope=False, attention_multiplier=c["attention_multiplier"],
        embedding_multiplier=float(c["embedding_multiplier"]),
        residual_multiplier=c["residual_multiplier"],
        logits_scaling=float(c["logits_scaling"]), tie_embeddings=True,
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
    """``{path: (shape, dtype, init)}`` of every leaf, in the program's
    layout: one scan group per period of ``pattern``, stacked on a
    leading axis.  The initialisation is the configuration's
    ``assumed.weights``."""
    m, md = dims(c), mamba_dims(c)
    d, h, kv, hd, v = m["d"], m["h"], m["kv"], m["hd"], m["vocab"]
    ff, sff = c["intermediate_size"], c["shared_intermediate_size"]
    held, s, wdt = c["num_local_experts"], c["socket"], c["torch_dtype"]
    kinds = pattern(c)
    g = m["layers"] // len(kinds)
    # q.k * attention_multiplier has a spread near 1 on unit-RMS inputs
    qk = (d * hd ** 0.5 * c["attention_multiplier"]) ** -0.5
    # the scaled embedding has RMS 1/32: the layers' sum outweighs it,
    # so the tied head does not always pick the token just read
    emb = 1 / (32 * c["embedding_multiplier"])
    out = {"embed/table": ((v, d), wdt, emb),
           "final_norm/scale": ((d,), "float32", "zeros")}
    for i, kind in enumerate(kinds):
        p = f"groups/slot_{i}/"
        out.update({
            p + "norm_mix/scale": ((g, d), "float32", "zeros"),
            p + "norm_mlp/scale": ((g, d), "float32", "zeros"),
            p + "moe/router": ((g, d, router_width(c)), "float32",
                               d ** -0.5),
            p + "moe/w_gate": ((g, held, d, ff), wdt, d ** -0.5),
            p + "moe/w_up": ((g, held, d, ff), wdt, d ** -0.5),
            p + "moe/w_down": ((g, held, ff, d), wdt, ff ** -0.5),
            p + "moe/shared/w_gate": ((g, d, sff), wdt, d ** -0.5),
            p + "moe/shared/w_up": ((g, d, sff), wdt, d ** -0.5),
            p + "moe/shared/w_down": ((g, sff, d), wdt, sff ** -0.5)})
        if kind == "attn":
            out.update({
                p + "attn/wq": ((g, d, h, hd), wdt, qk),
                p + "attn/wk": ((g, d, kv, hd), wdt, qk),
                p + "attn/wv": ((g, d, kv, hd), wdt, d ** -0.5),
                p + "attn/wo": ((g, h, hd, d), wdt, (h * hd) ** -0.5),
                p + "attn/hash_w": (
                    (g, s["num_tables"], s["num_planes"], hd), "float32",
                    1.0)})
        else:
            out.update({
                p + "mamba/in_proj": ((g, d, md["proj"]), wdt, d ** -0.5),
                p + "mamba/conv_w": ((g, md["conv"], md["width"]), wdt,
                                     0.5),
                p + "mamba/conv_b": ((g, md["conv"]), wdt, "zeros"),
                p + "mamba/A_log": ((g, md["nh"]), "float32", 1.0),
                p + "mamba/dt_bias": ((g, md["nh"]), "float32", "zeros"),
                p + "mamba/D": ((g, md["nh"]), "float32", "ones"),
                p + "mamba/norm_scale": ((g, md["di"]), "float32", "ones"),
                p + "mamba/out_proj": ((g, md["di"], d), wdt,
                                       md["di"] ** -0.5)})
    return out


def layer_weights(weights: dict, i: int) -> dict:
    """Layer ``i``'s leaves, flattened to ``{name: array}``: the mixer's
    (Mamba's or attention's), the MoE's (the shared expert's as
    ``shared_<name>``) and the two norms."""
    slots = len(weights["groups"])
    slot = weights["groups"][f"slot_{i % slots}"]
    j = i // slots
    mixer = slot["mamba"] if "mamba" in slot else slot["attn"]
    out = {k: a[j] for k, a in mixer.items()}
    moe = slot["moe"]
    out.update({k: moe[k][j] for k in ("router", "w_gate", "w_up",
                                        "w_down")})
    out.update({f"shared_{k}": a[j] for k, a in moe["shared"].items()})
    out["norm_mix"] = slot["norm_mix"]["scale"][j]
    out["norm_mlp"] = slot["norm_mlp"]["scale"][j]
    return out


def _routed_experts(c: dict) -> float:
    """Held experts a token routes to here, on average."""
    return c["num_experts_per_tok"] * c["num_local_experts"] / \
        router_width(c)


def _expert_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["intermediate_size"]


def matmul_params(c: dict) -> int:
    """Weights a token multiplies by: the mixers' projections, the
    routers, the held experts it routes to (on average), the shared
    experts and the head (the embedding is a lookup; norms, SSM
    parameters and hash planes are not weights of a product)."""
    d, md = c["hidden_size"], mamba_dims(c)
    m = dims(c)
    per = {"mamba": d * md["proj"] + md["di"] * d,
           "attn": d * (m["h"] + 2 * m["kv"]) * m["hd"]
           + m["h"] * m["hd"] * d}
    moe = (d * router_width(c) + _routed_experts(c) * _expert_params(c)
           + 3 * d * c["shared_intermediate_size"])
    return int(sum(per[k] + moe for k in layer_kinds(c)) + d * m["vocab"])


def state_bytes(c: dict, itemsize: int = 2) -> int:
    """One request's state in one Mamba layer: the SSD state (float32)
    and the conv tail."""
    md = mamba_dims(c)
    return 4 * md["nh"] * md["hd"] * md["st"] + \
        (md["width"] - 1) * md["conv"] * itemsize


def step(c: dict, decode_lengths, itemsize: int = 2):
    """(flops, bytes) of one decode step: a token for each request, at
    the given context lengths."""
    m, md, kinds = dims(c), mamba_dims(c), layer_kinds(c)
    d = m["d"]
    e, k, held = router_width(c), c["num_experts_per_tok"], \
        c["num_local_experts"]
    b = len(decode_lengths)
    size = {"bfloat16": itemsize, "float32": 4}
    weights = sum(int(np.prod(shape)) * size[dt] for path, (shape, dt, _)
                  in weight_shapes(c).items()
                  if path.split("/")[-1] not in ("w_gate", "w_up", "w_down")
                  or "/shared/" in path)
    experts_read = held * (1 - (1 - k / e) ** b)
    nbytes = weights + len(kinds) * experts_read * _expert_params(c) * \
        itemsize
    n_mamba = kinds.count("mamba")
    scan = md["nh"] * md["hd"] * md["st"]
    # the recurrence h * decay + (dt x) B^T (3 a state element) and the
    # readout C h (2), and the conv's taps
    mamba_flops = 5 * scan + 2 * md["conv"] * md["width"]
    flops_ = 0.0
    for n in decode_lengths:
        flops_ += 2.0 * matmul_params(c) + n_mamba * mamba_flops
        nbytes += d * itemsize                           # embedding row
        nbytes += n_mamba * 2 * state_bytes(c, itemsize)  # read, written
        f, by = flops.decode_token(c, n, itemsize)
        flops_ += kinds.count("attn") * f
        nbytes += kinds.count("attn") * by
    return flops_, nbytes
