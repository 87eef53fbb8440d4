"""DecodeBackend registry + KVView contract: contiguous-vs-paged parity
for every registered backend, O(top_k) K/V traffic on the paged SOCKET
path, and the Pallas kernel plumbing."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import attention as attn
from repro.models import backends as bk
from repro.models import param as pm

ALL_BACKENDS = ["socket", "hard_lsh", "quest", "dense"]
NB = 4            # blocks per request in the parity fixtures


def _cfg(backend):
    return get_config("stablelm-12b").smoke().replace(
        attention_backend=backend)


def _setup(backend, seed=0):
    """One attention layer's params + a filled contiguous cache and an
    identical-content paged pool (shuffled physical blocks)."""
    cfg = _cfg(backend)
    be = bk.get_backend(backend)
    rng = np.random.default_rng(seed)
    params = pm.unbox(attn.init_attention(cfg, jax.random.PRNGKey(seed)))
    kv = params["wk"].shape[1]
    b, hd = 2, cfg.head_dim
    bs = cfg.serving.block_size
    capacity = NB * bs

    keys = jnp.asarray(rng.normal(size=(b, kv, capacity, hd)), jnp.float32)
    vals = jnp.asarray(rng.normal(size=(b, kv, capacity, hd)), jnp.float32)
    cache = be.init_cache(cfg, b, kv, capacity, jnp.float32)
    cache = be.prefill_build(cfg, params, cache, keys, vals)

    # paged pool with the same logical content behind shuffled block ids
    num_blocks = 1 + b * NB                      # block 0 = trash
    pool = be.init_cache(cfg, num_blocks, kv, bs, jnp.float32)
    bt = 1 + rng.permutation(b * NB).reshape(b, NB).astype(np.int32)
    pages = {}
    for name, leaf in cache.items():
        rows_pb = pool[name].shape[2]
        p = np.asarray(pool[name]).copy()
        for i in range(b):
            for j in range(NB):
                p[bt[i, j]] = np.asarray(
                    leaf[i, :, j * rows_pb:(j + 1) * rows_pb])
        pages[name] = jnp.asarray(p)

    spec = be.cache_spec(cfg)
    cview = bk.ContiguousView(dict(cache), spec)
    pview = bk.PagedView(pages, spec, jnp.asarray(bt), block_size=bs)
    q = jnp.asarray(rng.normal(size=(b, kv, cfg.gqa_groups, 1, hd)),
                    jnp.float32)
    return cfg, be, params, cview, pview, q


def test_registry_contents():
    assert set(ALL_BACKENDS) <= set(bk.registered_backends())
    for name in ("socket", "hard_lsh", "quest"):
        assert bk.get_backend(name).supports_paged, name
    assert not bk.get_backend("dense").supports_paged
    with pytest.raises(ValueError, match="unknown attention backend"):
        bk.get_backend("flashinfer")


@pytest.mark.parametrize("backend", ALL_BACKENDS)
def test_attend_contiguous_paged_parity(backend):
    """attend through a PagedView must equal the ContiguousView bitwise at
    mixed ragged lengths (same logical content, shuffled physical pages)."""
    cfg, be, params, cview, pview, q = _setup(backend)
    lengths = jnp.asarray([13, 29], jnp.int32)
    out_c = be.attend(cfg, params, q, cview, length=lengths, scale=0.125)
    out_p = be.attend(cfg, params, q, pview, length=lengths, scale=0.125)
    np.testing.assert_array_equal(np.asarray(out_c), np.asarray(out_p))


@pytest.mark.parametrize("backend", ALL_BACKENDS)
def test_append_contiguous_paged_parity(backend):
    """append at ragged per-request positions must leave both views with
    identical logical leaf contents."""
    cfg, be, params, cview, pview, q = _setup(backend, seed=1)
    rng = np.random.default_rng(7)
    b, kv = 2, params["wk"].shape[1]
    kc = jnp.asarray(rng.normal(size=(b, kv, 1, cfg.head_dim)), jnp.float32)
    vc = jnp.asarray(rng.normal(size=(b, kv, 1, cfg.head_dim)), jnp.float32)
    pos = jnp.asarray([13, 29], jnp.int32)
    be.append(cfg, params, cview, kc, vc, pos)
    be.append(cfg, params, pview, kc, vc, pos)
    for name in cview.arrays:
        np.testing.assert_array_equal(
            np.asarray(cview.leaf(name)), np.asarray(pview.leaf(name)),
            err_msg=f"{backend}:{name}")


def test_paged_socket_gathers_only_topk_kv_rows():
    """The paged SOCKET attend must materialize only the small metadata
    leaves; K/V are touched at exactly the static top-k rows."""
    from repro.core import socket as sk

    cfg, be, params, _, pview, q = _setup("socket")
    bk.gather_trace_reset()
    be.attend(cfg, params, q, pview,
              length=jnp.asarray([13, 29], jnp.int32), scale=0.125)
    trace = bk.gather_trace()
    full_leaves = {name for kind, name, _ in trace if kind == "leaf"}
    assert full_leaves <= {"bits", "vnorm"}, trace
    kq = sk.topk_budget(bk.socket_config_of(cfg), pview.n_tokens)
    row_gathers = [t for t in trace if t[0] == "rows"]
    assert {name for _, name, _ in row_gathers} == {"k", "v"}
    for _, name, shape in row_gathers:
        assert shape[-2] == kq, (name, shape, kq)


@pytest.mark.parametrize("selection", ["kvhead", "pooled"])
def test_socket_kernel_plumbing_matches_xla_path(selection):
    """use_score_kernel / use_flash_decode route attend through the Pallas
    kernels (interpret mode off-TPU) with matching results."""
    cfg, be, params, cview, pview, q = _setup("socket")
    cfg = cfg.replace(socket=dataclasses.replace(cfg.socket,
                                                 selection=selection))
    out_ref = be.attend(cfg, params, q, cview,
                        length=jnp.int32(29), scale=0.125)
    cfg_k = cfg.replace(socket=dataclasses.replace(
        cfg.socket, use_score_kernel=True, use_flash_decode=True))
    for view in (cview, pview):
        out_k = be.attend(cfg_k, params, q, view,
                          length=jnp.int32(29), scale=0.125)
        np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_ref),
                                   atol=2e-5)


def test_socket_kernel_scores_int8_bits():
    """The scoring kernel now handles int8 ±1 sign storage (the
    uint32-word assumption was the only blocker): kernel-scored attend
    must match the plain-XLA scoring path on the same int8 cache."""
    cfg, be, params, _, _, q = _setup("socket")
    cfg8 = cfg.replace(socket=dataclasses.replace(
        cfg.socket, bits_storage="int8"))
    be8 = bk.get_backend("socket")
    rng = np.random.default_rng(3)
    kv, hd = params["wk"].shape[1], cfg.head_dim
    keys = jnp.asarray(rng.normal(size=(2, kv, 32, hd)), jnp.float32)
    vals = jnp.asarray(rng.normal(size=(2, kv, 32, hd)), jnp.float32)
    cache = be8.init_cache(cfg8, 2, kv, 32, jnp.float32)
    cache = be8.prefill_build(cfg8, params, cache, keys, vals)
    view = bk.ContiguousView(cache, be8.cache_spec(cfg8))
    outs = {}
    for use_kernel in (False, True):
        ck = cfg8.replace(socket=dataclasses.replace(
            cfg8.socket, use_score_kernel=use_kernel))
        outs[use_kernel] = be8.attend(ck, params, q, view,
                                      length=jnp.int32(16), scale=0.125)
    np.testing.assert_allclose(np.asarray(outs[True]),
                               np.asarray(outs[False]), atol=2e-5)


@pytest.mark.parametrize("selection", ["kvhead", "pooled", "qhead"])
def test_paged_socket_scoring_routes_to_kernel_where_it_compiles(
        selection, monkeypatch):
    """With no flag set, kvhead/pooled scoring runs the Pallas kernel
    wherever it compiles (a TPU) and records its dispatch; qhead keeps
    the XLA scorer.  On the CPU's default routing nothing is recorded;
    with the compile predicate patched, the kernel runs here in the
    interpreter and the attend output matches the XLA route."""
    from repro.kernels import common as kcommon

    cfg, be, params, _, pview, q = _setup("socket")
    cfg = cfg.replace(socket=dataclasses.replace(cfg.socket,
                                                 selection=selection))
    lengths = jnp.asarray([13, 29], jnp.int32)

    def attend():
        bk.gather_trace_reset()
        out = be.attend(cfg, params, q, pview, length=lengths, scale=0.125)
        return out, [t for t in bk.gather_trace() if t[0] == "fused"]

    out_xla, fused = attend()
    assert fused == []
    monkeypatch.setattr(kcommon, "compiles_with_mosaic", lambda: True)
    out_k, fused = attend()
    if selection == "qhead":
        assert fused == []
        np.testing.assert_array_equal(np.asarray(out_k),
                                      np.asarray(out_xla))
    else:
        b, kvh = pview.block_table.shape[0], params["wk"].shape[1]
        assert fused == [("fused", "socket_score",
                          (b, kvh, pview.n_tokens))]
        np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_xla),
                                   atol=2e-5)


def test_quest_append_resets_stats_on_reused_page():
    """A decode-growth block may be a reused page still carrying the
    previous owner's min/max (BlockPool never scrubs device memory): the
    first token written into a page must RESET the stats, not merge."""
    cfg, be, params, cview, pview, q = _setup("quest", seed=2)
    ps = cfg.quest.page_size
    bs = cfg.serving.block_size
    # poison every stats page with huge stale bounds
    poison = {"kmin": jnp.full_like(pview.arrays["kmin"], -1e4),
              "kmax": jnp.full_like(pview.arrays["kmax"], 1e4)}
    pview.arrays.update(poison)
    kv, hd = params["wk"].shape[1], cfg.head_dim
    kc = jnp.ones((2, kv, 1, hd), jnp.float32) * 0.5
    pos = jnp.asarray([0, bs], jnp.int32)            # page-opening writes
    be.append(cfg, params, pview, kc, kc, pos)
    for i, p in enumerate([0, bs]):
        row = np.asarray(pview.leaf("kmin"))[i, :, p // ps]
        np.testing.assert_array_equal(row, 0.5)      # reset, not min(-1e4,·)
        row = np.asarray(pview.leaf("kmax"))[i, :, p // ps]
        np.testing.assert_array_equal(row, 0.5)
    # mid-page writes still merge
    be.append(cfg, params, pview, kc * 3, kc * 3, pos + 1)
    np.testing.assert_array_equal(
        np.asarray(pview.leaf("kmax"))[0, :, 0], 1.5)
    np.testing.assert_array_equal(
        np.asarray(pview.leaf("kmin"))[0, :, 0], 0.5)


@pytest.mark.parametrize("selection", ["kvhead", "pooled"])
def test_socket_backend_matches_reference_socket_attend(selection):
    """The backend's attend composition must stay pinned to the reference
    ``core.socket.socket_attend`` oracle (used by the context-parallel
    tests and accuracy benchmarks)."""
    import dataclasses

    from repro.core import socket as sk

    cfg, be, params, cview, _, q = _setup("socket")
    cfg = cfg.replace(socket=dataclasses.replace(cfg.socket,
                                                 selection=selection))
    out_b = be.attend(cfg, params, q, cview, length=jnp.int32(29),
                      scale=0.125)
    out_ref = sk.socket_attend(
        bk.socket_config_of(cfg), params["hash_w"], q, cview.arrays["k"],
        cview.arrays["v"],
        sk.SocketCache(bits=cview.arrays["bits"],
                       vnorm=cview.arrays["vnorm"]),
        length=jnp.int32(29), scale=0.125)
    np.testing.assert_allclose(np.asarray(out_b), np.asarray(out_ref),
                               atol=1e-6)


def test_quest_page_size_must_divide_block_size():
    cfg = _cfg("quest")
    bad = cfg.replace(quest=dataclasses.replace(cfg.quest, page_size=3))
    with pytest.raises(ValueError, match="divide serving block_size"):
        bk.get_backend("quest").cache_spec(bad)


def test_cache_spec_drives_cache_and_axes():
    """init_attention_cache / cache_logical_axes are derived from the
    backend spec — leaf set, page granularity and dtypes must line up."""
    cfg = _cfg("quest")
    cache = attn.init_attention_cache(cfg, batch=2, capacity=32, attn_type="global")
    ps = cfg.quest.page_size
    assert set(cache) == {"k", "v", "kmin", "kmax"}
    assert cache["kmin"].shape[2] == 32 // ps
    assert bool(jnp.all(jnp.isinf(cache["kmin"])))
    axes = attn.cache_logical_axes(cfg, "global")
    assert axes["kmin"] == ("cache_batch", "cache_heads", "cache_seq", None)

    cfg_s = _cfg("socket")
    cache_s = attn.init_attention_cache(cfg_s, batch=2, capacity=32,
                                        attn_type="global")
    assert set(cache_s) == {"k", "v", "bits", "vnorm"}
    assert cache_s["bits"].dtype == jnp.uint32
    assert attn.cache_logical_axes(cfg_s, "global")["vnorm"] == (
        "cache_batch", "cache_heads", "cache_seq")


FUSED_BACKENDS = ["socket", "hard_lsh", "quest"]


def _fused_cfg(cfg, backend):
    """Flip the backend's fused-paged gate (hard_lsh shares SOCKET's)."""
    if backend == "quest":
        return cfg.replace(quest=dataclasses.replace(
            cfg.quest, use_paged_kernel=True))
    return cfg.replace(socket=dataclasses.replace(
        cfg.socket, use_paged_kernel=True))


def _count_pool_gathers(fn, *args, num_blocks):
    """# of XLA gather eqns (recursively) whose operand is a pool leaf."""
    jaxpr = jax.make_jaxpr(fn)(*args)

    def walk(jx):
        hits = 0
        for eqn in jx.eqns:
            if eqn.primitive.name == "gather":
                op = eqn.invars[0].aval
                if op.ndim >= 3 and op.shape[0] == num_blocks:
                    hits += 1
            for sub in jax.core.jaxprs_in_params(eqn.params):
                hits += walk(sub)
        return hits
    return walk(jaxpr.jaxpr)


@pytest.mark.parametrize("backend", FUSED_BACKENDS)
def test_fused_paged_attend_has_zero_pool_gathers(backend):
    """The fused kernels consume the pool in place: the attend jaxpr must
    contain ZERO gather primitives on pool-shaped operands, where the
    unfused paged path needs them for every leaf view / top-k row fetch."""
    cfg, be, params, _, pview, q = _setup(backend)
    num_blocks = pview.arrays["k"].shape[0]
    lengths = jnp.asarray([13, 29], jnp.int32)

    def attend(cfg):
        def fn(q, pages, bt):
            view = bk.PagedView(pages, be.cache_spec(cfg), bt,
                                block_size=cfg.serving.block_size)
            return be.attend(cfg, params, q, view, length=lengths,
                             scale=0.125)
        return fn

    unfused = _count_pool_gathers(attend(cfg), q, pview.arrays,
                                  pview.block_table, num_blocks=num_blocks)
    assert unfused >= 2, "unfused paged path should gather K and V rows"

    fused = _count_pool_gathers(attend(_fused_cfg(cfg, backend)), q,
                                pview.arrays, pview.block_table,
                                num_blocks=num_blocks)
    assert fused == 0, f"fused path launched {fused} pool gathers"


@pytest.mark.parametrize("selection", ["kvhead", "pooled"])
def test_fused_paged_kernel_matches_unfused_paged_path(selection):
    """use_paged_kernel routes PagedView attends through the fused Pallas
    kernel with matching results (ragged and scalar lengths); contiguous
    views keep the existing path bit-for-bit."""
    cfg, be, params, cview, pview, q = _setup("socket")
    cfg = cfg.replace(socket=dataclasses.replace(cfg.socket,
                                                 selection=selection))
    cfg_f = cfg.replace(socket=dataclasses.replace(cfg.socket,
                                                   use_paged_kernel=True))
    for length in (jnp.asarray([13, 29], jnp.int32), jnp.int32(29)):
        out_ref = be.attend(cfg, params, q, pview, length=length,
                            scale=0.125)
        out_f = be.attend(cfg_f, params, q, pview, length=length,
                          scale=0.125)
        np.testing.assert_allclose(np.asarray(out_f), np.asarray(out_ref),
                                   atol=2e-5)
    # the flag must not disturb contiguous callers at all
    out_c = be.attend(cfg, params, q, cview, length=jnp.int32(29),
                      scale=0.125)
    out_cf = be.attend(cfg_f, params, q, cview, length=jnp.int32(29),
                       scale=0.125)
    np.testing.assert_array_equal(np.asarray(out_c), np.asarray(out_cf))


def test_fused_paged_kernel_rejects_unsupported_combos():
    """int8 bit storage, per-q-head selection and non-sublane block sizes
    have no fused path — they must fail fast, not score garbage."""
    cfg, be, params, _, pview, q = _setup("socket")
    lengths = jnp.asarray([13, 29], jnp.int32)
    base_s = dataclasses.replace(cfg.socket, use_paged_kernel=True)

    cfg8 = cfg.replace(socket=dataclasses.replace(base_s,
                                                  bits_storage="int8"))
    with pytest.raises(NotImplementedError, match="int8"):
        be.attend(cfg8, params, q, pview, length=lengths, scale=0.125)

    cfgq = cfg.replace(socket=dataclasses.replace(base_s,
                                                  selection="qhead"))
    with pytest.raises(NotImplementedError, match="per-q-head"):
        be.attend(cfgq, params, q, pview, length=lengths, scale=0.125)

    cfg_bs = cfg.replace(socket=base_s)
    bad_view = bk.PagedView(pview.arrays, be.cache_spec(cfg_bs),
                            pview.block_table, block_size=12)
    with pytest.raises(NotImplementedError, match="block_size"):
        be.attend(cfg_bs, params, q, bad_view, length=lengths, scale=0.125)


@pytest.mark.parametrize("backend", ["hard_lsh", "quest"])
def test_new_fused_backends_match_unfused_paged_path(backend):
    """use_paged_kernel routes hard_lsh / quest PagedView attends through
    their fused Pallas kernels with matching results (ragged and scalar
    lengths); contiguous views keep the existing path bit-for-bit."""
    cfg, be, params, cview, pview, q = _setup(backend)
    cfg_f = _fused_cfg(cfg, backend)
    for length in (jnp.asarray([13, 29], jnp.int32), jnp.int32(29)):
        out_ref = be.attend(cfg, params, q, pview, length=length,
                            scale=0.125)
        out_f = be.attend(cfg_f, params, q, pview, length=length,
                          scale=0.125)
        np.testing.assert_allclose(np.asarray(out_f), np.asarray(out_ref),
                                   atol=2e-5)
    out_c = be.attend(cfg, params, q, cview, length=jnp.int32(29),
                      scale=0.125)
    out_cf = be.attend(cfg_f, params, q, cview, length=jnp.int32(29),
                       scale=0.125)
    np.testing.assert_array_equal(np.asarray(out_c), np.asarray(out_cf))


@pytest.mark.parametrize("backend", FUSED_BACKENDS)
def test_fused_backends_report_zero_paged_bytes(backend):
    """Every fused gate flips its backend's fused_paged() and zeroes the
    per-step paged-pool gather accounting (hard_lsh used to ignore the
    flag — it now fuses through SOCKET's gate)."""
    from repro.serving.paged import gather_footprint

    cfg = _cfg(backend)
    fp = gather_footprint(cfg)
    assert not fp["fused_paged_kernel"]
    assert fp["paged_bytes_per_step"] > 0

    cfg_f = _fused_cfg(cfg, backend)
    assert bk.get_backend(backend).fused_paged(cfg_f)
    fp = gather_footprint(cfg_f)
    assert fp["fused_paged_kernel"]
    assert fp["paged_bytes_per_step"] == 0


def test_config_time_kernel_gate_validation():
    """Every fused-gate combination the Pallas kernels would reject at
    trace time (deep inside a jitted serving step) is rejected by
    ``cfg.validate()`` — and therefore by ``cache_plan()``, the serving
    engine's first config touch — with the offending flag pair named."""
    cfg = _cfg("socket")
    fused_s = dataclasses.replace(cfg.socket, use_paged_kernel=True)

    bad = cfg.replace(socket=dataclasses.replace(fused_s,
                                                 bits_storage="int8"))
    with pytest.raises(ValueError, match="bits_storage"):
        bad.validate()
    with pytest.raises(ValueError, match="use_paged_kernel"):
        bad.cache_plan()
    bad = cfg.replace(socket=dataclasses.replace(fused_s,
                                                 selection="qhead"))
    with pytest.raises(ValueError, match="selection"):
        bad.validate()
    bad = cfg.replace(socket=fused_s, serving=dataclasses.replace(
        cfg.serving, block_size=12))
    with pytest.raises(ValueError, match="block_size"):
        bad.validate()

    qcfg = _cfg("quest")
    fused_q = dataclasses.replace(qcfg.quest, use_paged_kernel=True)
    bad = qcfg.replace(quest=fused_q, serving=dataclasses.replace(
        qcfg.serving, block_size=12))
    with pytest.raises(ValueError, match="block_size"):
        bad.validate()
    bad = qcfg.replace(quest=dataclasses.replace(fused_q, page_size=3))
    with pytest.raises(ValueError, match="page_size"):
        bad.validate()

    bad = cfg.replace(use_ring_kernel=True, serving=dataclasses.replace(
        cfg.serving, block_size=12))
    with pytest.raises(ValueError, match="use_ring_kernel"):
        bad.validate()

    # the eligible smoke gates stay constructible
    cfg.replace(socket=fused_s).validate()
    qcfg.replace(quest=fused_q).validate()
    cfg.replace(use_ring_kernel=True).validate()


def test_ragged_cp_decode_falls_back_to_xla_path():
    """Ragged decode + ``decode_cp_axes`` used to raise a bare
    NotImplementedError mid-serve; it must now warn once (via obs) and
    produce the pjit/XLA result bit-for-bit.  Scalar-length decode keeps
    the shard_map fast path (covered by test_distributed)."""
    import repro.serving.obs as obs
    from repro.distributed import sharding as shd

    cfg, be, params, cview, _, q = _setup("socket")
    lengths = jnp.asarray([13, 29], jnp.int32)
    out_plain = be.attend(cfg, params, q, cview, length=lengths,
                          scale=0.125)

    cfg_cp = cfg.replace(decode_cp_axes=("data",))
    mesh = jax.make_mesh((1,), ("data",))
    obs._WARNED.discard("socket-ragged-cp-fallback")
    with shd.activate_mesh(mesh):
        with pytest.warns(UserWarning, match="ragged decode"):
            out_cp = be.attend(cfg_cp, params, q, cview, length=lengths,
                               scale=0.125)
        # one-shot: the fallback must not spam every decode step
        import warnings
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            out_again = be.attend(cfg_cp, params, q, cview, length=lengths,
                                  scale=0.125)
    np.testing.assert_array_equal(np.asarray(out_plain), np.asarray(out_cp))
    np.testing.assert_array_equal(np.asarray(out_plain),
                                  np.asarray(out_again))
