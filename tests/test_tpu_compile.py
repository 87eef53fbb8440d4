"""Compile the Pallas kernels for a TPU v5e described in software.

Interpret mode runs a kernel body as plain JAX ops, so it cannot see
what Mosaic refuses: block shapes that break the (8, 128) tiling, casts
and reshapes the TPU has no lowering for, more scoped VMEM than a kernel
may use.  These tests compile each kernel of the served path for one
chip of a described ``v5e:2x2`` topology at the serving engine's widths
(minitron-8b: 8 KV heads, GQA group 4, head_dim 128; SOCKET P=10, L=60;
16-token pages; 512-entry block tables, and 8,192 for the 128k-token
case) and assert that the compiled program holds the Mosaic kernel
(``tpu_custom_call``).  Nothing runs; no chip is needed.

The topology is described inside a module fixture — never at import —
so every pytest worker collects the same tests and only the worker that
runs this file loads the TPU compiler.  The persistent compile cache is
off around these compiles: entries built for a described chip cannot be
read back without one.
"""

import functools

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.flash_decode import flash_decode
from repro.kernels.paged_attention import (paged_hard_lsh_attend,
                                           paged_quest_attend,
                                           paged_ring_attend,
                                           paged_socket_attend)
from repro.kernels.socket_score import socket_score
from repro.kernels.socket_score.socket_score import socket_score_pallas

B, KVH, G, HD = 8, 8, 4, 128           # decode batch, minitron-8b heads
BS, P, L, W = 16, 10, 60, 20           # page rows, SOCKET planes/tables/words
NB_POOL = 1 + 2048                     # 32k-token pool + trash block
SCALE = HD ** -0.5


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    with pytest.MonkeyPatch.context() as mp:
        # keep the TPU compiler's logs out of the shared temp directory
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:           # no TPU compiler in this install
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        prev = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        yield SingleDeviceSharding(topo.devices[0])
        jax.config.update("jax_enable_compilation_cache", prev)
        compilation_cache.reset_cache()


def _compiled_text(one_chip, fn, *shapes) -> str:
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _pool(dtype=jnp.bfloat16, rows=HD):
    return (NB_POOL, KVH, BS, rows), dtype


_Q = ((B, KVH, G, 1, HD), jnp.bfloat16)
_VEC = ((B,), jnp.int32)
_SCALES = ((NB_POOL, KVH, BS), jnp.float32)


def _socket_shapes(nb, kv_dtype):
    return [_Q, _pool(kv_dtype), _pool(kv_dtype), _pool(jnp.uint32, W),
            ((NB_POOL, KVH, BS), jnp.bfloat16), ((B, KVH, G, L, P),
                                                 jnp.float32),
            ((B, nb), jnp.int32), _VEC, _VEC]


def _paged_socket(nb, kv_dtype=jnp.bfloat16):
    quantized = kv_dtype != jnp.bfloat16

    def fn(q, k, v, bits, vn, u, bt, ln, bud, *scales):
        ks, vs = scales if quantized else (None, None)
        return paged_socket_attend(
            q, k, v, bits, vn, u, bt, length=ln, budget=bud, num_tables=L,
            num_planes=P, tau=0.4, scale=SCALE, sink_tokens=128,
            window_tokens=128, interpret=False, k_scale=ks, v_scale=vs)

    shapes = _socket_shapes(nb, kv_dtype)
    return fn, shapes + ([_SCALES, _SCALES] if quantized else [])


def _paged_hard_lsh(nb):
    def fn(q, k, v, bits, vn, u, bt, ln, bud):
        return paged_hard_lsh_attend(
            q, k, v, bits, vn, u, bt, length=ln, budget=bud, num_tables=L,
            num_planes=P, scale=SCALE, sink_tokens=128, window_tokens=128,
            interpret=False)
    return fn, _socket_shapes(nb, jnp.bfloat16)


def _paged_quest(nb, kv_dtype=jnp.bfloat16):
    quantized = kv_dtype != jnp.bfloat16

    def fn(q, k, v, kmin, kmax, bt, ln, *scales):
        ks, vs = scales if quantized else (None, None)
        return paged_quest_attend(
            q, k, v, kmin, kmax, bt, length=ln, page_budget=52,
            page_size=BS, scale=SCALE, sink_tokens=128, window_tokens=128,
            interpret=False, k_scale=ks, v_scale=vs)

    stats = ((NB_POOL, KVH, 1, HD), jnp.float32)
    shapes = [_Q, _pool(kv_dtype), _pool(kv_dtype), stats, stats,
              ((B, nb), jnp.int32), _VEC]
    return fn, shapes + ([_SCALES, _SCALES] if quantized else [])


def _paged_ring(kv_dtype=jnp.bfloat16):
    quantized = kv_dtype != jnp.bfloat16

    def fn(q, k, v, bt, pos, *scales):
        ks, vs = scales if quantized else (None, None)
        return paged_ring_attend(q, k, v, bt, pos=pos, window=1024,
                                 softcap=0.0, scale=SCALE, interpret=False,
                                 k_scale=ks, v_scale=vs)

    shapes = [_Q, _pool(kv_dtype), _pool(kv_dtype), ((B, 64), jnp.int32),
              _VEC]
    return fn, shapes + ([_SCALES, _SCALES] if quantized else [])


def _socket_score():
    n = 512 * BS

    def fn(bits, u, vnorm):
        return socket_score(bits, u, vnorm, num_tables=L, num_planes=P,
                            tau=0.4, interpret=False)
    return fn, [((B, KVH, n, W), jnp.uint32), ((B, KVH, G, L, P),
                                               jnp.float32),
                ((B, KVH, n), jnp.float32)]


def _socket_score_cell():
    """The kernel as the served step calls it at the mistral-7b-v0.3
    decode-long cell's shape: 4 requests, 8 KV heads, group 4, an
    8,192-token ceiling, the packed words key-major (B, N, KVH, W) as
    the block-table gather writes them, and per-request lengths."""
    b, n = 4, 8192

    def fn(words, u, length):
        return socket_score_pallas(words, u, None, num_tables=L,
                                   num_planes=P, tau=0.4, length=length,
                                   interpret=False)
    return fn, [((b, n, KVH, W), jnp.uint32),
                ((b, KVH, G, L, P), jnp.float32), ((b,), jnp.int32)]


def _flash_decode():
    k = 832                              # a top-k selection width

    def fn(q, kk, vv, mask):
        return flash_decode(q, kk, vv, mask, scale=SCALE, interpret=False)
    return fn, [_Q, ((B, KVH, k, HD), jnp.bfloat16),
                ((B, KVH, k, HD), jnp.bfloat16), ((B, KVH, k), jnp.bool_)]


KERNELS = {
    "paged_socket_bf16": functools.partial(_paged_socket, 512),
    "paged_socket_int8": functools.partial(_paged_socket, 512, jnp.int8),
    "paged_socket_bf16_128k": functools.partial(_paged_socket, 8192),
    "paged_hard_lsh_bf16": functools.partial(_paged_hard_lsh, 512),
    "paged_quest_bf16": functools.partial(_paged_quest, 512),
    "paged_quest_int8": functools.partial(_paged_quest, 512, jnp.int8),
    "paged_ring_bf16": _paged_ring,
    "paged_ring_int8": functools.partial(_paged_ring, jnp.int8),
    "socket_score": _socket_score,
    "socket_score_cell": _socket_score_cell,
    "flash_decode": _flash_decode,
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = KERNELS[name]()
    assert "tpu_custom_call" in _compiled_text(one_chip, fn, *shapes)
