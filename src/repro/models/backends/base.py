"""DecodeBackend protocol + KVView abstraction for sparse decode attention.

A **decode backend** owns one global-attention layer's cache layout and the
three operations the model/engine needs:

* ``cache_spec(cfg)``      — declarative leaf layout (:class:`LeafSpec`):
                             trailing shape, dtype, sequence **granularity**
                             (tokens per row — Quest metadata is
                             page-granular), and init fill value.
* ``prefill_build(...)``   — write the prompt's K/V rows + backend metadata
                             into a freshly allocated contiguous cache.
* ``append(...)``          — write one new token (K/V + metadata) through a
                             :class:`KVView` at logical position ``pos``.
* ``attend(...)``          — decode attention for one query step against a
                             :class:`KVView`.

``attend``/``append`` never touch array layout directly: they go through a
:class:`KVView`, which has two realizations.  :class:`ContiguousView` wraps
the standard ``(B, KVH, N, ...)`` cache; :class:`PagedView` wraps the
serving engine's page pool ``(num_blocks, KVH, block_size, ...)`` plus a
per-request block table, translating logical token indices through the
table.  Either view holds the **layer-stacked** leaves (a leading layer
axis, as the layer scan carries them) and the index of its layer: reads
gather with the layer as the first index and writes update the new rows
in place (whole pages on the pool, :func:`update_pages`), so no layer's
slice is ever cut out of the stack and written back.  A backend whose
``attend`` reads full K/V only through ``gather_rows`` (top-k
selection) is **paged-capable**
(``supports_paged``): the engine then never materializes contiguous K/V
views — per step it moves only the small metadata leaves plus
``O(top_k)`` K/V rows.

Paged-view reads are recorded in a trace-time log (:func:`gather_trace`)
so tests and benchmarks can assert exactly which leaves a backend
materializes per decode step.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.backends import kvquant

__all__ = ["LeafSpec", "LayerCacheSpec", "KVView", "ContiguousView",
           "PagedView", "RingView", "DecodeBackend", "LayerCacheHandler",
           "PagedKVCacheHandler", "kv_leaf_specs", "write_prefill_kv",
           "subset_attention", "gather_trace", "gather_trace_reset",
           "record_fused", "gather_block_leaf", "write_block_prefill",
           "write_chunk_blocks", "write_chunk_rows", "ring_write_page",
           "kv_quant_mode", "write_token_kv", "gather_kv_rows",
           "dequant_leaf", "effective_keys", "kv_scales_of"]


def gather_block_leaf(pages: jax.Array, bt: jax.Array,
                      layer=None) -> jax.Array:
    """Materialize a paged leaf's logical view through a block table:
    ``(NB, KVH, rows_pb, *rest), (B, nb) -> (B, KVH, nb*rows_pb, *rest)``.
    With ``layer``, ``pages`` is a layer-stacked leaf ``(L, NB, ...)``
    read at that layer by the same single gather.

    The one implementation of the pool layout's logical flattening —
    shared by :meth:`PagedView.leaf`, the serving engine's dense
    fallback (``serving.paged.gather_views``), and the fused paged
    kernel's test oracle."""
    b, nb = bt.shape
    g = pages[bt] if layer is None else pages[layer, bt]
    g = jnp.moveaxis(g, 2, 1)              # (B, KVH, nb, rows_pb, *rest)
    return g.reshape(b, g.shape[1], nb * g.shape[3], *g.shape[4:])


def set_rows(a: jax.Array, layer, row, value: jax.Array) -> jax.Array:
    """Write ``value[b]`` ``(KVH, *suffix)`` at ``a[layer, b, :, row[b]]``
    of a layer-stacked contiguous cache ``(L, B, KVH, rows, *suffix)``:
    one dynamic-update-slice per request, which XLA does in place."""
    value = value.astype(a.dtype)
    for b in range(value.shape[0]):
        a = jax.lax.dynamic_update_slice(
            a, value[b][None, None, :, None],
            (layer, b, 0, row[b]) + (0,) * (a.ndim - 4))
    return a


def update_pages(a: jax.Array, layer, blk, fn) -> jax.Array:
    """Replace page ``a[layer, blk[i]]`` ``(KVH, rows_pb, *suffix)`` of a
    layer-stacked pool leaf by ``fn(i, page)``, for each request ``i``.

    Each page is read and written whole, at the same place: the
    tile-aligned form the TPU (and XLA on the CPU) updates in place in a
    loop-carried leaf.  Writing a single row, or scattering over the
    leaf's non-adjacent axes, makes the compiler copy or relayout the
    whole leaf instead."""
    pages = a[layer, blk]                  # (B, KVH, rows_pb, *suffix)
    return set_pages(a, layer, blk, jnp.stack(
        [fn(i, pages[i]) for i in range(blk.shape[0])]))


def put_row(page: jax.Array, row, value: jax.Array) -> jax.Array:
    """``page`` ``(KVH, rows_pb, *suffix)`` with row ``row`` set to
    ``value`` ``(KVH, *suffix)``."""
    hit = (jnp.arange(page.shape[1]) == row).reshape(
        1, -1, *([1] * (page.ndim - 2)))
    return jnp.where(hit, value[:, None].astype(page.dtype), page)


def set_pages(a: jax.Array, layer, blk, pages: jax.Array) -> jax.Array:
    """Write whole pages ``pages[i]`` ``(KVH, rows_pb, *suffix)`` at
    ``a[layer, blk[i]]`` of a layer-stacked pool leaf, in place."""
    pages = pages.astype(a.dtype)
    for i in range(pages.shape[0]):
        a = jax.lax.dynamic_update_slice(
            a, pages[i][None, None], (layer, blk[i]) + (0,) * (a.ndim - 2))
    return a


@dataclasses.dataclass(frozen=True)
class LeafSpec:
    """Layout of one cache leaf along ``(batch, KVH, seq_rows, *suffix)``.

    ``granularity`` is tokens per sequence row: 1 for token-granular leaves
    (k, v, bits, vnorm), ``page_size`` for Quest's per-page min/max
    statistics.  A capacity of ``N`` tokens allocates ``ceil(N /
    granularity)`` rows.  ``dtype is None`` means "use the cache compute
    dtype"; ``fill`` is the zero/identity init value (±inf for min/max).

    ``per_head=False`` folds the KV heads into the row: the head axis has
    length 1 and ``suffix`` leads with the heads, so a pool page is
    ``(1, rows, KVH, ...)`` — key-major, each token's heads side by side.
    """

    suffix: Tuple[int, ...] = ()
    dtype: Optional[Any] = None
    granularity: int = 1
    fill: float = 0.0
    per_head: bool = True

    def rows(self, capacity: int) -> int:
        return -(-capacity // self.granularity)

    def heads(self, kv_heads: int) -> int:
        """Length of the leaf's head axis."""
        return kv_heads if self.per_head else 1

    def axes(self, seq_axis: str) -> Tuple:
        """Logical sharding axes of the leaf ``(batch, heads, rows,
        *suffix)``: the KV heads shard wherever they lie."""
        if self.per_head:
            return ("cache_batch", "cache_heads", seq_axis) + \
                (None,) * len(self.suffix)
        return ("cache_batch", None, seq_axis, "cache_heads") + \
            (None,) * (len(self.suffix) - 1)

    def row_width(self, kv_heads: int) -> int:
        """Elements of one row across all KV heads."""
        return self.heads(kv_heads) * int(np.prod(self.suffix,
                                                  dtype=np.int64))

    def leaf_dtype(self, cache_dtype) -> Any:
        return cache_dtype if self.dtype is None else jnp.dtype(self.dtype)


def kv_quant_mode(cfg) -> str:
    """The resolved K/V storage mode for attention layers (paged and
    ring alike — ``cfg.cache_plan()`` resolves the same knob; Mamba state
    never quantizes)."""
    return getattr(cfg.serving, "kv_dtype", "auto")


def kv_leaf_specs(cfg) -> Dict[str, LeafSpec]:
    """The K/V leaves every backend stores.

    Under ``serving.kv_dtype`` ``"int8"``/``"fp8"`` the k/v leaves store
    quantized rows and a float32 per-row scale leaf rides along
    (``k_scale``/``v_scale``, empty suffix, granularity 1 — exactly how
    the SOCKET bits/vnorm side-cache rides along), produced/consumed via
    :mod:`repro.models.backends.kvquant`.  ``"bf16"`` is a plain storage
    cast (no scales); ``"auto"`` keeps the compute dtype.
    """
    hd = cfg.head_dim
    kvd = kv_quant_mode(cfg)
    if kvd == "auto":
        return {"k": LeafSpec(suffix=(hd,)), "v": LeafSpec(suffix=(hd,))}
    sdt = kvquant.storage_dtype(kvd, None)
    spec = {"k": LeafSpec(suffix=(hd,), dtype=sdt),
            "v": LeafSpec(suffix=(hd,), dtype=sdt)}
    if kvquant.is_quantized(kvd):
        spec["k_scale"] = LeafSpec(suffix=(), dtype=kvquant.scale_dtype())
        spec["v_scale"] = LeafSpec(suffix=(), dtype=kvquant.scale_dtype())
    return spec


def kv_scales_of(arrays: Dict[str, jax.Array], name: str):
    """The scale leaf paired with K/V leaf ``name`` (None when the cache
    is unquantized)."""
    return arrays.get(name + "_scale")


@dataclasses.dataclass(frozen=True)
class LayerCacheSpec:
    """One layer's resolved cache layout on the serving engine's pool.

    * ``kind == "paged"`` — leaves live in pool pages addressed linearly
      through the request block table (global-attention backends).
    * ``kind == "ring"`` — K/V pages addressed circularly through the
      first ``ring_blocks`` block-table entries (sliding-window layers).
    * ``kind == "state"`` — fixed per-decode-slot leaves (batch axis =
      slots), no block table at all (Mamba conv tail + SSD state);
      ``leaves`` is empty — shapes come from the Mamba cache builder.
    """

    kind: str
    leaves: Dict[str, LeafSpec]
    ring_blocks: int = 0


# --------------------------------------------------------------------- trace

# Trace-time log of paged-view materializations: each PagedView.leaf /
# gather_rows call appends (kind, leaf_name, shape) while the enclosing
# function is being traced.  Tests assert the SOCKET paged path never
# materializes full "k"/"v" leaves; the serving benchmark turns the shapes
# into per-step gathered bytes.
_GATHER_TRACE = []


def gather_trace_reset() -> None:
    _GATHER_TRACE.clear()


def gather_trace():
    return list(_GATHER_TRACE)


def record_fused(name: str, shape) -> None:
    """Log a fused-kernel dispatch (kind ``"fused"``): the attend consumed
    the pool + block table in place — zero leaf materializations, zero
    K/V row gathers.  Lets the zero-gather tests distinguish "the fused
    path ran" from "the paged path was never exercised"."""
    _GATHER_TRACE.append(("fused", name, tuple(shape)))


# --------------------------------------------------------------------- views

class KVView:
    """Uniform read/write interface over one layer's decode cache.

    ``arrays`` maps leaf name -> layer-stacked array (a leading layer
    axis; the rest of the layout depends on the subclass) and ``layer``
    is this view's index into it (traced inside the layer scan).
    Writes replace entries in ``arrays`` functionally (the dict mutates,
    the arrays never do) — callers read back ``view.arrays`` as the
    updated cache pytree.
    """

    def __init__(self, arrays: Dict[str, jax.Array],
                 spec: Dict[str, LeafSpec], *, layer):
        self.arrays = dict(arrays)
        self.spec = spec
        self.layer = layer

    def layer_arrays(self) -> Dict[str, jax.Array]:
        """This layer's slice of every leaf — a copy of each, for the
        fused paged kernels, which take a pool leaf whole."""
        return {name: a[self.layer] for name, a in self.arrays.items()}

    # ---- reads
    @property
    def n_tokens(self) -> int:
        """Logical token capacity of the view."""
        raise NotImplementedError

    def leaf(self, name: str) -> jax.Array:
        """Full logical-layout leaf ``(B, KVH, rows, *suffix)``."""
        raise NotImplementedError

    def gather_rows(self, name: str, idx: jax.Array) -> jax.Array:
        """Rows of a token-granular leaf at logical indices ``idx``
        ``(B, KVH, *sel)`` -> ``(B, KVH, *sel, *suffix)``."""
        raise NotImplementedError

    # ---- writes (one token at logical position pos: scalar or (B,))
    def write_token(self, name: str, pos: jax.Array,
                    value: jax.Array) -> None:
        """Set the row covering token ``pos`` to ``value`` (B, KVH, *suffix)."""
        raise NotImplementedError

    def rmw_token(self, name: str, pos: jax.Array, fn) -> None:
        """Read-modify-write the row covering token ``pos`` (Quest min/max):
        ``row <- fn(row)``."""
        raise NotImplementedError

    # ---- helpers
    def _pos_vec(self, pos: jax.Array, batch: int) -> jax.Array:
        pos = jnp.asarray(pos, jnp.int32)
        return jnp.broadcast_to(pos, (batch,)) if pos.ndim == 0 else pos


class ContiguousView(KVView):
    """Today's layout: each leaf is ``(L, B, KVH, rows, *suffix)``."""

    @property
    def n_tokens(self) -> int:
        return self.arrays["k"].shape[3] * self.spec["k"].granularity

    def leaf(self, name: str) -> jax.Array:
        return self.arrays[name][self.layer]

    def gather_rows(self, name: str, idx: jax.Array) -> jax.Array:
        assert self.spec[name].granularity == 1, name
        a = self.arrays[name]
        b, kvh = a.shape[1:3]
        bidx = jnp.arange(b).reshape(b, *([1] * (idx.ndim - 1)))
        hidx = jnp.arange(kvh).reshape(1, kvh, *([1] * (idx.ndim - 2)))
        return a[self.layer, bidx, hidx, idx]

    def _row(self, name: str, pos: jax.Array):
        a = self.arrays[name]
        pos = self._pos_vec(pos, a.shape[1])
        return a, pos // self.spec[name].granularity

    # Scalar pos (lockstep batch) writes the whole batch's row in one
    # dynamic-update-slice; a ragged (B,) position vector writes one per
    # request (:func:`set_rows`).
    def write_token(self, name, pos, value) -> None:
        a = self.arrays[name]
        if jnp.ndim(pos) == 0:
            row = jnp.asarray(pos, jnp.int32) // self.spec[name].granularity
            start = (self.layer, 0, 0, row) + (0,) * (a.ndim - 4)
            self.arrays[name] = jax.lax.dynamic_update_slice(
                a, value[None, :, :, None].astype(a.dtype), start)
            return
        a, row = self._row(name, pos)
        self.arrays[name] = set_rows(a, self.layer, row, value)

    def rmw_token(self, name, pos, fn) -> None:
        a = self.arrays[name]
        if jnp.ndim(pos) == 0:
            row = jnp.asarray(pos, jnp.int32) // self.spec[name].granularity
            start = (self.layer, 0, 0, row) + (0,) * (a.ndim - 4)
            old = jax.lax.dynamic_slice(
                a, start, (1,) + a.shape[1:3] + (1,) + a.shape[4:])
            self.arrays[name] = jax.lax.dynamic_update_slice(
                a, fn(old[0, :, :, 0])[None, :, :, None].astype(a.dtype),
                start)
            return
        a, row = self._row(name, pos)
        bidx = jnp.arange(a.shape[1])
        self.arrays[name] = set_rows(a, self.layer, row,
                                     fn(a[self.layer, bidx, :, row]))


class PagedView(KVView):
    """Serving-engine layout: each leaf is ``(L, num_blocks, KVH,
    block_size / granularity, *suffix)`` plus a per-request block table
    ``(B, blocks_per_seq)`` of physical block ids (trash-padded).

    Logical token ``t`` of request ``b`` lives in physical block
    ``block_table[b, t // block_size]`` at row ``(t % block_size) //
    granularity`` of the view's layer.  ``leaf()`` materializes the full
    logical view (cheap for metadata leaves, what paged-capable backends
    avoid for K/V); ``gather_rows`` translates selected logical indices
    through the table and touches only those rows.
    """

    def __init__(self, arrays, spec, block_table: jax.Array,
                 block_size: int, *, layer):
        super().__init__(arrays, spec, layer=layer)
        self.block_table = block_table
        self.block_size = block_size

    @property
    def n_tokens(self) -> int:
        return self.block_table.shape[1] * self.block_size

    def leaf(self, name: str) -> jax.Array:
        out = gather_block_leaf(self.arrays[name], self.block_table,
                                self.layer)
        _GATHER_TRACE.append(("leaf", name, out.shape))
        return out

    def _gather(self, name: str, idx: jax.Array, entry) -> jax.Array:
        """Rows at logical indices ``idx`` ``(B, KVH, *sel)`` whose
        block-table entries are ``entry(idx)``."""
        pages = self.arrays[name]
        bt = self.block_table
        b, kvh = bt.shape[0], pages.shape[2]
        bidx = jnp.arange(b).reshape(b, *([1] * (idx.ndim - 1)))
        hidx = jnp.arange(kvh).reshape(1, kvh, *([1] * (idx.ndim - 2)))
        return pages[self.layer, bt[bidx, entry(idx)], hidx,
                     idx % self.block_size]

    def gather_rows(self, name: str, idx: jax.Array) -> jax.Array:
        assert self.spec[name].granularity == 1, name
        out = self._gather(name, idx, lambda i: i // self.block_size)
        _GATHER_TRACE.append(("rows", name, out.shape))
        return out

    def _addr(self, name: str, pos: jax.Array):
        pages = self.arrays[name]
        pos = self._pos_vec(pos, self.block_table.shape[0])
        bidx = jnp.arange(self.block_table.shape[0])
        blk = self.block_table[bidx, pos // self.block_size]
        row = (pos % self.block_size) // self.spec[name].granularity
        return pages, blk, row

    def write_token(self, name, pos, value) -> None:
        pages, blk, row = self._addr(name, pos)
        self.arrays[name] = update_pages(
            pages, self.layer, blk,
            lambda i, page: put_row(page, row[i], value[i]))

    def rmw_token(self, name, pos, fn) -> None:
        pages, blk, row = self._addr(name, pos)
        new = fn(pages[self.layer, blk, :, row])
        self.arrays[name] = update_pages(
            pages, self.layer, blk,
            lambda i, page: put_row(page, row[i], new[i]))


def ring_write_page(pages: jax.Array, layer, blk: jax.Array,
                    pos: jax.Array, value: jax.Array, *, block_size: int,
                    ring_blocks: int, window: int) -> jax.Array:
    """Write token ``pos``'s ``value`` (B, KVH, *suffix) into its circular
    page ``blk`` (B,) of layer ``layer`` of the stacked leaf ``pages``
    ``(L, NB, KVH, block_size, *suffix)`` at row ``pos % block_size``,
    **scrubbing rows that cannot hold in-window tokens at page-opening
    writes** (row 0):

    * first pass over the ring (``pos < ring capacity``): the page is a
      freshly allocated pool block still carrying its previous owner's
      data, and no row past the one written can be valid yet (they map
      to negative positions) — zero it all;
    * later passes: rows ``[1, capacity - window]`` hold positions that
      fell out of the window the moment this page reopened — zero that
      dead band, keep the still-live window rows (``capacity - window <
      block_size`` always, since ``capacity = ceil(window / block_size)
      * block_size``).

    Ring validity masking already excludes every scrubbed row from
    attention; the scrub exists so pool contents are a pure function of
    the live requests — recycled blocks are never zeroed on device
    otherwise.  Active slots hold disjoint blocks; only trash-page
    writes alias (their content is never read unmasked)."""
    cap = ring_blocks * block_size
    row = pos % block_size
    r = jnp.arange(block_size)
    scrub = (row == 0)[:, None] & (r[None] >= 1) & (
        (r[None] <= cap - window) | (pos < cap)[:, None])   # (B, bs)

    def put(i, page):                      # (KVH, block_size, *suffix)
        dead = scrub[i].reshape(1, block_size, *([1] * (page.ndim - 2)))
        page = jnp.where(dead, jnp.zeros((), page.dtype), page)
        return put_row(page, row[i], value[i])

    return update_pages(pages, layer, blk, put)


class RingView(PagedView):
    """Sliding-window ring over pool pages: the first ``ring_blocks``
    block-table entries form a circular page list — logical token ``t``
    lives at entry ``(t // block_size) % ring_blocks``, row
    ``t % block_size`` (so flat ring slot ``t % (ring_blocks *
    block_size)``).  Old pages are recycled in place; per-slot block
    demand never exceeds ``ring_blocks``.

    ``leaf()`` materializes the *bounded* ring view (``ring_blocks *
    block_size`` rows — window-sized, never context-sized), recorded in
    the gather trace under kind ``"ring"`` so the zero-materialization
    assertions for paged K/V stay meaningful.  ``window`` drives the
    page-opening scrub of :func:`ring_write_page`.
    """

    def __init__(self, arrays, spec, block_table: jax.Array,
                 block_size: int, ring_blocks: int, window: int, *, layer):
        super().__init__(arrays, spec, block_table, block_size, layer=layer)
        self.ring_blocks = ring_blocks
        self.window = window

    @property
    def n_tokens(self) -> int:
        return self.ring_blocks * self.block_size

    def leaf(self, name: str) -> jax.Array:
        out = gather_block_leaf(self.arrays[name],
                                self.block_table[:, :self.ring_blocks],
                                self.layer)
        _GATHER_TRACE.append(("ring", name, out.shape))
        return out

    def _entry(self, t: jax.Array) -> jax.Array:
        """Block-table entry of logical token ``t``."""
        return (t // self.block_size) % self.ring_blocks

    def _addr(self, name: str, pos: jax.Array):
        assert self.spec[name].granularity == 1, name
        pages = self.arrays[name]
        pos = self._pos_vec(pos, self.block_table.shape[0])
        bidx = jnp.arange(self.block_table.shape[0])
        return pages, self.block_table[bidx, self._entry(pos)], \
            pos % self.block_size

    def gather_rows(self, name: str, idx: jax.Array) -> jax.Array:
        out = self._gather(name, idx, self._entry)
        _GATHER_TRACE.append(("ring", name, out.shape))
        return out

    def write_token(self, name, pos, value) -> None:
        pages, blk, _ = self._addr(name, pos)
        pos = self._pos_vec(pos, self.block_table.shape[0])
        self.arrays[name] = ring_write_page(
            pages, self.layer, blk, pos, value, block_size=self.block_size,
            ring_blocks=self.ring_blocks, window=self.window)


# ------------------------------------------------------------------ backend

def write_prefill_kv(cfg, cache: Dict[str, jax.Array], kc: jax.Array,
                     vc: jax.Array) -> Dict[str, jax.Array]:
    """Write the prompt K/V ``(B, KVH, T, hd)`` into rows [0, T),
    quantizing on write (absmax per row, inside the caller's jit — no
    extra HBM round-trip) when the cache carries scale leaves."""
    t = kc.shape[2]
    kvd = kv_quant_mode(cfg)
    cache = dict(cache)
    if kvquant.is_quantized(kvd):
        kq, ks = kvquant.quantize(kc, kvd)
        vq, vs = kvquant.quantize(vc, kvd)
        cache["k"] = cache["k"].at[:, :, :t].set(kq)
        cache["v"] = cache["v"].at[:, :, :t].set(vq)
        cache["k_scale"] = cache["k_scale"].at[:, :, :t].set(ks)
        cache["v_scale"] = cache["v_scale"].at[:, :, :t].set(vs)
        return cache
    cache["k"] = cache["k"].at[:, :, :t].set(kc.astype(cache["k"].dtype))
    cache["v"] = cache["v"].at[:, :, :t].set(vc.astype(cache["v"].dtype))
    return cache


def write_token_kv(cfg, view: "KVView", pos: jax.Array, kc: jax.Array,
                   vc: jax.Array) -> None:
    """Append-side K/V write of one token ``(B, KVH, hd)`` through a
    view, quantizing on write when the cache carries scale leaves."""
    kvd = kv_quant_mode(cfg)
    if kvquant.is_quantized(kvd):
        kq, ks = kvquant.quantize(kc, kvd)
        vq, vs = kvquant.quantize(vc, kvd)
        view.write_token("k", pos, kq)
        view.write_token("v", pos, vq)
        view.write_token("k_scale", pos, ks)
        view.write_token("v_scale", pos, vs)
        return
    view.write_token("k", pos, kc)
    view.write_token("v", pos, vc)


def gather_kv_rows(cfg, view: "KVView", idx: jax.Array):
    """The unfused paths' K/V read: gather the O(top_k) selected rows and
    dequantize ONLY those (the quantized pool rows never round-trip
    through HBM at full precision).  Returns ``(k_sel, v_sel)`` in
    float32 under quantization, storage dtype otherwise."""
    k_sel = view.gather_rows("k", idx)
    v_sel = view.gather_rows("v", idx)
    if kvquant.is_quantized(kv_quant_mode(cfg)):
        k_sel = kvquant.dequantize(k_sel, view.gather_rows("k_scale", idx))
        v_sel = kvquant.dequantize(v_sel, view.gather_rows("v_scale", idx))
    return k_sel, v_sel


def dequant_leaf(cfg, view: "KVView", name: str) -> jax.Array:
    """Full logical K/V leaf, dequantized when the cache carries scale
    leaves (dense fallback / probe shadow path — the fused kernels never
    take this route)."""
    a = view.leaf(name)
    if name in ("k", "v") and kvquant.is_quantized(kv_quant_mode(cfg)):
        return kvquant.dequantize(a, view.leaf(name + "_scale"))
    return a


def effective_keys(cfg, kc: jax.Array) -> jax.Array:
    """The key values the attend phase will actually read back: the
    quantization round trip of ``kc`` under int8/fp8 storage, ``kc``
    itself otherwise.  Quest's kmin/kmax page stats are computed from
    this (``quest.stats_from_quantized``) so the per-page bounds cover
    the dequantized keys and the upper-bound score stays sound."""
    kvd = kv_quant_mode(cfg)
    if kvquant.is_quantized(kvd) and cfg.quest.stats_from_quantized:
        return kvquant.dequantize(*kvquant.quantize(kc, kvd))
    return kc


def subset_attention(cfg, q: jax.Array, k_sel: jax.Array, v_sel: jax.Array,
                     sel_mask: jax.Array, *, scale: float) -> jax.Array:
    """Exact attention over a gathered subset, routed through the Pallas
    ``flash_decode`` kernel when ``cfg.socket.use_flash_decode`` is set
    (interpret mode off-TPU) and the layout is the shared-KV one."""
    if cfg.socket.use_flash_decode and k_sel.ndim == 4:
        from repro.kernels.flash_decode import ops as fd_ops
        return fd_ops.flash_decode(q, k_sel, v_sel, sel_mask, scale=scale)
    from repro.core import socket as sk
    return sk.sparse_attention_over_subset(q, k_sel, v_sel, sel_mask,
                                           scale=scale)


class DecodeBackend:
    """One decode-attention backend (see module docstring).

    Subclasses set ``name`` (registry key) and ``supports_paged`` (True
    iff ``attend`` reads K/V only via ``gather_rows`` so the serving
    engine can skip contiguous-view materialization entirely).
    """

    name: str = ""
    supports_paged: bool = False

    # ---- layout ---------------------------------------------------------
    def cache_spec(self, cfg) -> Dict[str, LeafSpec]:
        raise NotImplementedError

    def init_cache(self, cfg, batch: int, kv_heads: int, capacity: int,
                   dtype) -> Dict[str, jax.Array]:
        """Allocate one layer's contiguous cache from the spec."""
        out = {}
        for name, s in self.cache_spec(cfg).items():
            out[name] = jnp.full(
                (batch, s.heads(kv_heads), s.rows(capacity), *s.suffix),
                s.fill, s.leaf_dtype(dtype))
        return out

    def cache_axes(self, cfg, seq_axis: str) -> Dict[str, Tuple]:
        """Logical sharding axes mirroring :meth:`init_cache`."""
        return {name: s.axes(seq_axis)
                for name, s in self.cache_spec(cfg).items()}

    # ---- ops ------------------------------------------------------------
    def prefill_build(self, cfg, params, cache: Dict[str, jax.Array],
                      kc: jax.Array, vc: jax.Array) -> Dict[str, jax.Array]:
        """Write prompt K/V ``(B, KVH, T, hd)`` + metadata into ``cache``."""
        raise NotImplementedError

    def append(self, cfg, params, view: KVView, kc: jax.Array,
               vc: jax.Array, pos: jax.Array) -> None:
        """Write one token's K/V ``(B, KVH, 1, hd)`` + metadata at ``pos``
        (scalar or per-request ``(B,)`` vector) through the view."""
        raise NotImplementedError

    def attend(self, cfg, params, q: jax.Array, view: KVView, *,
               length, scale: float) -> jax.Array:
        """Decode attention for ``q`` ``(B, KVH, G, 1, hd)`` against the
        view's first ``length`` tokens (scalar or ragged ``(B,)``).
        Per-request sparsity budgets are derived from ``length`` when it
        is a vector."""
        raise NotImplementedError

    # ---- accounting -----------------------------------------------------
    def selected_rows(self, cfg, n: int) -> int:
        """Static K/V rows gathered per step at capacity ``n`` (for the
        memory-traffic accounting in :func:`repro.serving.paged
        .gather_footprint`)."""
        return n

    def fused_paged(self, cfg) -> bool:
        """True when this backend's PagedView attend runs as one fused
        kernel over the pool — zero XLA gathers, zero materialized
        views, so the gather-footprint accounting reports ≈ 0."""
        return False


# --------------------------------------------------------- cache handlers

def write_block_prefill(pages: jax.Array, leaf: jax.Array,
                        bt_row: jax.Array) -> jax.Array:
    """Scatter a batch=1 prefill cache leaf ``(1, KVH, rows, *rest)`` into
    pool pages addressed by ``bt_row`` (block ids, trash-padded; only the
    first ``rows / rows_per_block`` entries are consumed)."""
    kvh, rows = leaf.shape[1], leaf.shape[2]
    rows_pb = pages.shape[2]
    nb = rows // rows_pb
    blocks = leaf[0].reshape(kvh, nb, rows_pb, *leaf.shape[3:])
    blocks = jnp.moveaxis(blocks, 1, 0)      # (nb, KVH, rows_pb, *rest)
    return pages.at[bt_row[:nb]].set(blocks.astype(pages.dtype))


def write_chunk_blocks(pages: jax.Array, layer, leaf: jax.Array,
                       bt_row: jax.Array, block0) -> jax.Array:
    """Write one *prefill chunk's* batch=1 cache leaf ``(1, KVH, rows,
    *rest)`` into layer ``layer`` of the stacked pool leaf ``pages`` at
    block-table offset ``block0`` (a traced scalar — the chunk's first
    logical block, ``history // block_size``), a whole page at a time:
    the chunked analogue of :func:`write_block_prefill`.  ``bt_row`` must
    be padded so ``block0 + rows / rows_per_block`` never exceeds its
    static length (entries past the request's allocation are trash)."""
    kvh, rows = leaf.shape[1], leaf.shape[2]
    rows_pb = pages.shape[3]
    nb = rows // rows_pb
    blocks = leaf[0].reshape(kvh, nb, rows_pb, *leaf.shape[3:])
    blocks = jnp.moveaxis(blocks, 1, 0)      # (nb, KVH, rows_pb, *rest)
    ids = jax.lax.dynamic_slice(bt_row, (jnp.asarray(block0, jnp.int32),),
                                (nb,))
    return set_pages(pages, layer, ids, blocks)


def write_chunk_rows(pages: Dict[str, jax.Array], layer,
                     leaves: Dict[str, jax.Array], bt_row: jax.Array,
                     history, last_index) -> Dict[str, jax.Array]:
    """Row-granular variant of :func:`write_chunk_blocks`, for the leaves
    ``pages`` (name -> layer-stacked pool leaf) and the chunk's batch=1
    cache leaves ``leaves`` ``(1, KVH, rows, *rest)``: chunk token ``i``
    lands at logical position ``history + i``, i.e. row ``(history + i) %
    rows_per_block`` of block ``bt_row[(history + i) // rows_per_block]``
    of layer ``layer``.  Needed when the chunk start is **not**
    page-aligned — a prefix-cache hit resumes prefill mid-page after the
    shared tail page is CoW-cloned — and only valid for granularity-1
    leaves (per-token rows; page-granular stats can't be written by the
    row).

    Each page the chunk may touch (one more than a page-aligned chunk
    fills) is written whole — the tile-aligned form the TPU writes in
    place.  Only two pages can hold rows the chunk does not write, the
    first (rows before ``history``) and the one with the chunk's last
    real row (rows past ``last_index``, final-chunk padding): those two
    are read first and keep those rows.  A page with no chunk row goes
    to the trash page, so no junk lands in real blocks."""
    out = {}
    for name, a in pages.items():
        leaf = leaves[name]
        rows, rows_pb = leaf.shape[2], a.shape[3]
        nb = rows // rows_pb + 1
        h = jnp.asarray(history, jnp.int32)
        li = jnp.asarray(last_index, jnp.int32)
        first = h // rows_pb + jnp.arange(nb, dtype=jnp.int32)
        i = first[:, None] * rows_pb + \
            jnp.arange(rows_pb, dtype=jnp.int32)[None] - h   # chunk row
        keep = (i >= 0) & (i <= li)
        ids = jnp.where(jnp.any(keep, axis=1), bt_row[first], 0)
        last = (h % rows_pb + li) // rows_pb     # page of the last row

        def page(j):
            return jax.lax.dynamic_slice(
                a, (layer, ids[j]) + (0,) * (a.ndim - 2),
                (1, 1) + a.shape[2:])[0, 0]
        j = jnp.arange(nb).reshape(nb, 1, 1, *([1] * (a.ndim - 4)))
        old = jnp.where(j == 0, page(0)[None], page(last)[None])
        vals = leaf[0][:, jnp.clip(i, 0, rows - 1)].astype(a.dtype)
        vals = jnp.moveaxis(vals, 1, 0)        # (nb, KVH, rows_pb, *rest)
        keep = keep.reshape(nb, 1, rows_pb, *([1] * (a.ndim - 4)))
        merged = jnp.where(keep, vals, old)
        out[name] = jax.lax.fori_loop(
            0, nb, lambda j, a, m=merged, ids=ids:
            jax.lax.dynamic_update_slice(
                a, m[j][None, None], (layer, ids[j]) + (0,) * (a.ndim - 2)),
            a)
    return out


class LayerCacheHandler:
    """Pool-side operations for ONE layer of the per-layer cache plan.

    The serving engine's pool helpers (:mod:`repro.serving.paged`) resolve
    each layer to a handler (``layer_cache_handler``) and dispatch through
    this interface; grouped (scan-stacked) layers are lifted over the
    group axis with ``jax.vmap`` by the caller.  All methods operate on
    one layer's leaf dict (name -> array).

    * ``spec``          — declarative :class:`LayerCacheSpec`.
    * ``write_prefill`` — scatter a fresh batch=1 prefill cache into the
                          pool (pages via ``bt_row`` or slot row ``slot``).
    * ``gather``        — materialize the contiguous per-slot views the
                          unmodified (non-paged) decode path consumes.
    * ``scatter``       — write the row(s) a decode step updated in those
                          views back into the pool.
    """

    kind: str = ""

    def spec(self, cfg) -> LayerCacheSpec:
        raise NotImplementedError

    def write_prefill(self, cfg, pages: Dict[str, jax.Array],
                      cache: Dict[str, jax.Array], bt_row: jax.Array,
                      slot: jax.Array) -> Dict[str, jax.Array]:
        raise NotImplementedError

    def gather(self, cfg, pages: Dict[str, jax.Array],
               bt: jax.Array) -> Dict[str, jax.Array]:
        raise NotImplementedError

    def scatter(self, cfg, pages: Dict[str, jax.Array],
                views: Dict[str, jax.Array], bt: jax.Array,
                pos: jax.Array) -> Dict[str, jax.Array]:
        raise NotImplementedError


class PagedKVCacheHandler(LayerCacheHandler):
    """Global-attention layers: the decode backend's ``cache_spec`` leaves
    in pool pages, block table consumed linearly (unchanged layout)."""

    kind = "paged"

    def __init__(self, backend: DecodeBackend):
        self.backend = backend

    def spec(self, cfg) -> LayerCacheSpec:
        return LayerCacheSpec(kind="paged",
                              leaves=self.backend.cache_spec(cfg))

    def write_prefill(self, cfg, pages, cache, bt_row, slot):
        del slot
        return {name: write_block_prefill(pages[name], cache[name], bt_row)
                for name in pages}

    def gather(self, cfg, pages, bt):
        return {name: gather_block_leaf(p, bt) for name, p in pages.items()}

    def scatter(self, cfg, pages, views, bt, pos):
        """Write the row each slot updated at token index ``pos[b]`` (view
        row ``pos // gran``) into physical page ``bt[b, pos //
        block_size]``.  Inactive slots point at the trash block; duplicate
        trash writes are benign."""
        bs = cfg.serving.block_size
        spec = self.backend.cache_spec(cfg)
        b = bt.shape[0]
        bidx = jnp.arange(b)
        blk = bt[bidx, pos // bs]
        out = {}
        for name, p in pages.items():
            gran = spec[name].granularity
            row = views[name][bidx, :, pos // gran]   # (B, KVH, *rest)
            out[name] = p.at[blk, :, (pos % bs) // gran].set(
                row.astype(p.dtype))
        return out
