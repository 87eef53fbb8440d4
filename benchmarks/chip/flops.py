"""Operations and HBM bytes the algorithm needs, from shapes alone.

What is counted is the work the served computation requires, not what
the program happens to do: the layer and head weights; for a decode
token, SOCKET's query hash, its scores over the hash bits and value
norms of every cached key up to the request's length, and exact
attention over the selected rows.  Temporaries a program
materializes (unpacked signs, padded views) are not work, so they show
as distance from the roofline.  Bytes assume each weight is read once
per step and each cache row once per use.
"""

from __future__ import annotations

import math

from benchmarks.chip.model import dims


def hash_words(sock: dict) -> int:
    """uint32 words of one key's packed hash bits: ``L*P`` bits rounded
    up so the words hold a whole number of ``P``-bit tables."""
    bits = sock["num_tables"] * sock["num_planes"]
    w = -(-bits // 32)
    while (w * 32) % sock["num_planes"]:
        w += 1
    return w


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


def budget(sock: dict, n: int) -> int:
    forced = min(n, sock["sink_tokens"] + sock["window_tokens"])
    return min(max(math.ceil(n / sock["sparsity"]), forced, sock["min_k"]), n)


def decode_token(c: dict, n: int, itemsize: int = 2):
    """(flops, cache bytes) of one decode token attending a context of
    ``n`` keys, its own included, over every layer; weights excluded."""
    m, s = dims(c), c["socket"]
    h, kv, hd = m["h"], m["kv"], m["hd"]
    lp = s["num_tables"] * s["num_planes"]
    k = budget(s, n)
    per_layer = (2 * h * lp * hd          # soft-hash the query heads
                 + 2 * kv * lp * hd       # hash the new key
                 + 2 * h * n * lp         # score every cached key
                 + 4 * h * k * hd)        # attend over the selected rows
    row = kv * (2 * hd * itemsize + 4 * hash_words(s) + 2)
    per_layer_bytes = (n * kv * (4 * hash_words(s) + 2)   # bits, vnorm
                       + k * kv * 2 * hd * itemsize       # selected K, V
                       + row)                              # new row
    return m["layers"] * per_layer, m["layers"] * per_layer_bytes


def step(c: dict, decode_lengths, itemsize: int = 2):
    """(flops, bytes) of one decode step: a token for each request, at
    the given context lengths.  Weights are read once a step."""
    wf = weight_flops_per_token(c)
    flops = 0.0
    nbytes = weight_bytes(c, itemsize)
    for n in decode_lengths:
        f, b = decode_token(c, n, itemsize)
        flops += wf + f
        nbytes += b + dims(c)["d"] * itemsize            # embedding row
    return flops, nbytes
