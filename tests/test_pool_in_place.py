"""The serving steps update the paged pool in place.

The layer scan carries the pool and each layer writes only its new rows,
addressing its layer of the stacked leaves through a layer-indexed view:

* the compiled decode-only and mixed steps of the test data's dense and
  hybrid configurations hold no temporary of the pool's size and copy no
  whole pool leaf;
* a layer-indexed view reads and writes exactly what the same view over
  that layer's slice alone would, and leaves the other layers as they
  were — paged, contiguous (ragged and lockstep), ring and quantized
  leaves;
* the SOCKET bits leaf stores a token's heads side by side (key-major
  pages), each head's hash words padded to the 128 lanes.
"""

import dataclasses
import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(REPO)]

from benchmarks.chip import model  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.core import hashing  # noqa: E402
from repro.core import socket as sk  # noqa: E402
from repro.models import attention as attn  # noqa: E402
from repro.models import backends as bk  # noqa: E402
from repro.models import param as pm  # noqa: E402
from repro.models.backends import kvquant  # noqa: E402
from repro.serving.engine import ContinuousBatchingEngine  # noqa: E402

DATA = REPO / "benchmarks/chip/tests/data"
GEOMETRY = {"block_size": 8, "max_blocks_per_seq": 16, "max_batch": 2,
            "pool_blocks": 1000, "prefill_chunk": 16}


@pytest.mark.parametrize("config,step", [
    (config, step) for config in ("tiny", "tiny-granite")
    for step in ("decode", "mixed")])
def test_step_programs_hold_no_pool_sized_temporaries(config, step):
    """At the parent of this form the decode step's temporaries were
    1.56x the pool (tiny) and 0.92x (tiny-granite): the layer scan cut
    each layer's slice out of the pool and wrote a second pool."""
    c = model.load_config(config, DATA)
    cfg = model.block(c, DATA).program_config(c, serving=GEOMETRY)
    eng = ContinuousBatchingEngine(cfg, rng=jax.random.PRNGKey(0))
    sv, i32 = cfg.serving, jnp.int32
    dec = (jnp.zeros((sv.max_batch, 1), i32),
           jnp.zeros((sv.max_batch, sv.max_blocks_per_seq), i32),
           jnp.zeros((sv.max_batch,), i32),
           jnp.zeros((sv.max_batch,), bool))
    if step == "decode":
        lowered = eng._decode_fn.lower(eng.params, eng.pages, eng._keys,
                                       *dec)
    else:
        lowered = eng._mixed_fn.lower(
            eng.params, eng.pages, eng._keys,
            jnp.zeros((1, sv.prefill_chunk), i32),
            jnp.zeros((eng._chunk_bt_len(),), i32), i32(0), i32(0),
            jnp.zeros((1,), i32), jnp.asarray(False), *dec)
    compiled = lowered.compile()
    leaves = jax.tree_util.tree_leaves(eng.pages)
    pool_bytes = sum(a.nbytes for a in leaves)
    assert compiled.memory_analysis().temp_size_in_bytes < 0.25 * pool_bytes
    shapes = {tuple(a.shape) for a in leaves}
    copies = []
    for line in compiled.as_text().splitlines():
        m = re.search(r"= \w+\[([\d,]*)\]\{[^}]*\} copy\(", line)
        if m and tuple(int(d) for d in m.group(1).split(",") if d) in shapes:
            copies.append(line.strip())
    assert not copies, copies


L, LAYER, NB, KVH, BS, D, B = 3, 1, 7, 2, 4, 3, 2
BT = np.array([[2, 5], [6, 1]], np.int32)          # (B, blocks per request)


def _stack(rng, shape, dtype=np.float32):
    """A leaf of ``L`` layers of random content."""
    return jnp.asarray(rng.normal(size=(L,) + shape).astype(dtype))


def _logical(pages, bt):
    """The old per-layer slice's logical view: ``(NB, KVH, rows, *rest)``
    through ``bt`` -> ``(B, KVH, nb * rows, *rest)``."""
    g = np.moveaxis(pages[bt], 2, 1)
    return g.reshape(g.shape[0], g.shape[1], -1, *g.shape[4:])


def _check_writes(view, before, want, layer_slice_writes):
    """Apply ``layer_slice_writes`` to the numpy copy ``want`` of the
    stacked leaves' layer ``LAYER`` and compare every layer."""
    for name, (pos, fn) in layer_slice_writes.items():
        fn(want[name][LAYER], np.asarray(pos))
    for name in want:
        np.testing.assert_array_equal(np.asarray(view.arrays[name]),
                                      want[name], err_msg=name)
        others = [i for i in range(L) if i != LAYER]
        np.testing.assert_array_equal(want[name][others],
                                      np.asarray(before[name])[others])


@pytest.mark.parametrize("kind", ["paged", "contiguous", "lockstep", "ring",
                                  "quantized"])
def test_layer_indexed_view_matches_the_layer_slice(kind):
    rng = np.random.default_rng(["paged", "contiguous", "lockstep", "ring",
                                 "quantized"].index(kind))
    value = rng.normal(size=(B, KVH, D)).astype(np.float32)
    idx = jnp.asarray(rng.integers(0, BT.shape[1] * BS, (B, KVH, 3)))
    bidx = np.arange(B)[:, None, None]
    hidx = np.arange(KVH)[None, :, None]
    pos = np.array([5, 2], np.int32)

    if kind in ("contiguous", "lockstep"):
        n = BT.shape[1] * BS
        spec = {"k": bk.LeafSpec(suffix=(D,)),
                "s": bk.LeafSpec(suffix=(D,), granularity=2)}
        arrays = {"k": _stack(rng, (B, KVH, n, D)),
                  "s": _stack(rng, (B, KVH, n // 2, D))}
        view = bk.ContiguousView(arrays, spec, layer=LAYER)
        ref = {name: np.array(a) for name, a in arrays.items()}
        np.testing.assert_array_equal(np.asarray(view.leaf("k")),
                                      ref["k"][LAYER])
        np.testing.assert_array_equal(
            np.asarray(view.gather_rows("k", idx)),
            ref["k"][LAYER][bidx, hidx, np.asarray(idx)])
        if kind == "lockstep":
            p = jnp.int32(6)
            view.write_token("k", p, jnp.asarray(value))
            view.rmw_token("s", p, lambda old: old * 2 + 1)

            def put(a, _):
                a[:, :, 6] = value

            def bump(a, _):
                a[:, :, 3] = a[:, :, 3] * 2 + 1
        else:
            view.write_token("k", jnp.asarray(pos), jnp.asarray(value))
            view.rmw_token("s", jnp.asarray(pos), lambda old: old * 2 + 1)

            def put(a, p):
                a[np.arange(B), :, p] = value

            def bump(a, p):
                r = p // 2
                a[np.arange(B), :, r] = a[np.arange(B), :, r] * 2 + 1
        _check_writes(view, arrays, ref, {"k": (pos, put),
                                          "s": (pos, bump)})
        return

    if kind == "ring":
        rb, window = 2, 6                      # ring of 8 rows, 6 live
        spec = {"k": bk.LeafSpec(suffix=(D,))}
        arrays = {"k": _stack(rng, (NB, KVH, BS, D))}
        view = bk.RingView(arrays, spec, jnp.asarray(BT), BS, rb, window,
                           layer=LAYER)
        ref = {"k": np.array(arrays["k"])}
        np.testing.assert_array_equal(np.asarray(view.leaf("k")),
                                      _logical(ref["k"][LAYER], BT[:, :rb]))
        # token 12 reopens its page on a later pass over the ring (rows
        # 1..cap-window scrubbed); token 3 lands mid-page
        pos = np.array([12, 3], np.int32)
        view.write_token("k", jnp.asarray(pos), jnp.asarray(value))

        def ring_put(a, p):
            for b in range(B):
                blk = BT[b, (p[b] // BS) % rb]
                row = p[b] % BS
                if row == 0:
                    dead = [r for r in range(1, BS)
                            if r <= rb * BS - window or p[b] < rb * BS]
                    a[blk, :, dead] = 0
                a[blk, :, row] = value[b]
        _check_writes(view, arrays, ref, {"k": (pos, ring_put)})
        return

    cfg = get_config("stablelm-12b").smoke()
    if kind == "quantized":
        cfg = cfg.replace(serving=dataclasses.replace(cfg.serving,
                                                      kv_dtype="int8"))
        spec = bk.kv_leaf_specs(cfg)
    else:
        spec = dict(bk.kv_leaf_specs(cfg),
                    s=bk.LeafSpec(suffix=(D,), granularity=2))
    arrays = {}
    for name, s in spec.items():
        shape = (NB, KVH, BS // s.granularity) + tuple(
            D if name in ("k", "v", "s") else w for w in s.suffix)
        arrays[name] = _stack(rng, shape, np.dtype(s.leaf_dtype(
            jnp.float32)))
    view = bk.PagedView(arrays, spec, jnp.asarray(BT), block_size=BS,
                        layer=LAYER)
    ref = {name: np.array(a) for name, a in arrays.items()}
    for name in arrays:
        np.testing.assert_array_equal(np.asarray(view.leaf(name)),
                                      _logical(ref[name][LAYER], BT))
    blk_of = BT[np.arange(B)[:, None, None], np.asarray(idx) // BS]
    np.testing.assert_array_equal(
        np.asarray(view.gather_rows("k", idx)),
        ref["k"][LAYER][blk_of, hidx, np.asarray(idx) % BS])
    blk = BT[np.arange(B), pos // BS]
    kc = jnp.asarray(value)
    bk.write_token_kv(cfg, view, jnp.asarray(pos), kc, kc * 2)
    if kind == "quantized":
        kq, ks = (np.asarray(x) for x in kvquant.quantize(kc, "int8"))
        vq, vs = (np.asarray(x) for x in kvquant.quantize(kc * 2, "int8"))
        rows = {"k": kq, "v": vq, "k_scale": ks, "v_scale": vs}
    else:
        view.rmw_token("s", jnp.asarray(pos), lambda old: old * 2 + 1)
        rows = {"k": value, "v": value * 2}

    def put_rows(name):
        def put(a, p):
            a[blk, :, p % BS] = rows[name]
        return put

    def bump(a, p):
        r = (p % BS) // 2
        a[blk, :, r] = a[blk, :, r] * 2 + 1
    writes = {name: (pos, put_rows(name)) for name in rows}
    if kind == "paged":
        writes["s"] = (pos, bump)
    _check_writes(view, arrays, ref, writes)


def test_socket_bits_pages_are_key_major():
    """A SOCKET bits page is ``(1, rows, KVH, 128)``: each token's heads
    side by side, each head's hash words zero-padded to the 128 lanes —
    the order in which the block-table gather hands the scoring kernel
    its words, and a minor axis the TPU keeps row-major.  An append
    writes the token's words there, in its layer only, and the view
    reads them back per head."""
    cfg = get_config("stablelm-12b").smoke().replace(
        attention_backend="socket")
    be, scfg = bk.get_backend("socket"), bk.socket_config_of(cfg)
    params = pm.unbox(attn.init_attention(cfg, jax.random.PRNGKey(0)))
    kv, bs = params["wk"].shape[1], cfg.serving.block_size
    w = hashing.num_words(scfg.num_tables, scfg.num_planes)
    pool = {name: jnp.stack([a, a]) for name, a in
            be.init_cache(cfg, NB, kv, bs, jnp.float32).items()}
    assert pool["bits"].shape == (2, NB, 1, bs, kv, 128)
    view = bk.PagedView(pool, be.cache_spec(cfg), jnp.asarray(BT),
                        block_size=bs, layer=1)
    kc = jnp.asarray(np.random.default_rng(0).normal(
        size=(B, kv, 1, cfg.head_dim)), jnp.float32)
    pos = np.array([bs + 3, 2], np.int32)
    be.append(cfg, params, view, kc, kc, jnp.asarray(pos))
    want = np.asarray(sk.precompute_key_hashes(
        scfg, params["hash_w"], kc, kc).bits)[:, :, 0]     # (B, KVH, W)
    bits = np.asarray(view.arrays["bits"])
    for b in range(B):
        row = bits[1, BT[b, pos[b] // bs], 0, pos[b] % bs]  # (KVH, 128)
        np.testing.assert_array_equal(row[:, :w], want[b])
        assert not row[:, w:].any()
    assert not bits[0].any()
    words = np.asarray(be._bits(scfg, view.leaf("bits")))  # (B, KVH, N, W)
    np.testing.assert_array_equal(words[np.arange(B), :, pos], want)
