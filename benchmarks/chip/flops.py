"""Operations and HBM bytes the algorithm needs, from shapes alone.

What is counted is the work the served computation requires, not what
the program happens to do.  This module holds what every SOCKET layer
shares: a decode token's query hash, its scores over the hash bits and
value norms of every cached key up to the request's length, and exact
attention over the selected rows.  A whole step (weights, and which
layers attend how) is its block's count (``blocks/<block>.py``).
Temporaries a program materializes (unpacked signs, padded views) are
not work, so they show as distance from the roofline.
"""

from __future__ import annotations

import math
from pathlib import Path

from benchmarks.chip import model


def hash_words(sock: dict) -> int:
    """uint32 words of one key's packed hash bits: ``L*P`` bits rounded
    up so the words hold a whole number of ``P``-bit tables."""
    bits = sock["num_tables"] * sock["num_planes"]
    w = -(-bits // 32)
    while (w * 32) % sock["num_planes"]:
        w += 1
    return w


def budget(sock: dict, n: int) -> int:
    forced = min(n, sock["sink_tokens"] + sock["window_tokens"])
    return min(max(math.ceil(n / sock["sparsity"]), forced, sock["min_k"]), n)


def decode_token(c: dict, n: int, itemsize: int = 2):
    """(flops, cache bytes) of one decode token attending a context of
    ``n`` keys, its own included, in one SOCKET attention layer; weights
    excluded.  Each cache row is read once per use."""
    m, s = model.dims(c), c["socket"]
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
    return per_layer, per_layer_bytes


def step(c: dict, decode_lengths, itemsize: int = 2,
         root: Path = model.HERE):
    """(flops, bytes) of one decode step: a token for each request, at
    the given context lengths, as configuration ``c``'s block counts
    it."""
    return model.block(c, root).step(c, decode_lengths, itemsize)
