"""Compile a cell's two engine steps for a described TPU v5e, without a
chip, and print what the compiler says they need in memory.

    JAX_PLATFORMS=cpu python3 benchmarks/chip/rehearse.py <cell> [pool_blocks ...]

For each pool size it lowers the decode-only and the mixed step at the
cell's geometry against shapes (no array is made at full size) and
prints one JSON line per step: argument, output, temporary and
generated-code bytes from ``memory_analysis()``, beside the chip's HBM.
This sizes a cell's pool before any chip time is spent; it measures
memory and legality, never time.
"""

from __future__ import annotations

import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(argv):
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchmarks.chip import harness, model
    from repro.serving import paged
    from repro.serving.engine import ContinuousBatchingEngine

    cell = harness.load_cell(argv[0], harness.load_manifest())
    pools = [int(x) for x in argv[1:]] or [cell.geometry["pool_blocks"]]
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def sds(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one),
            tree)

    blk = model.block(cell.config, cell.root)
    params = sds(jax.eval_shape(
        lambda: model.make_weights(cell.config, 0, cell.root)))
    small = blk.program_config(cell.config,
                               serving={**cell.geometry, "pool_blocks": 2})
    engine = ContinuousBatchingEngine(small, params=params)
    sv = small.serving
    b, nb, c = sv.max_batch, sv.max_blocks_per_seq, sv.prefill_chunk
    i32 = jnp.int32
    keys = jax.ShapeDtypeStruct(engine._keys.shape, engine._keys.dtype,
                                sharding=one)

    def arg(shape, dt=i32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one)

    dec = (arg((b, 1)), arg((b, nb)), arg((b,)), arg((b,), jnp.bool_))
    mix = (arg((1, c)), arg((engine._chunk_bt_len(),)), arg(()), arg(()),
           arg((1,)), arg((), jnp.bool_)) + dec
    for pool in pools:
        full = blk.program_config(cell.config, serving={
            **cell.geometry, "pool_blocks": pool})
        pages = sds(jax.eval_shape(
            lambda: paged.init_paged_caches(full, full.serving)))
        per_block = paged.pool_block_bytes(full)["per_block_id"]
        for kind, fn, args in (("decode", engine._decode_fn, dec),
                               ("mixed", engine._mixed_fn, mix)):
            ma = fn.lower(params, pages, keys, *args).compile() \
                .memory_analysis()
            need = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                    + ma.temp_size_in_bytes
                    + ma.generated_code_size_in_bytes
                    - ma.alias_size_in_bytes)
            print(json.dumps({
                "cell": cell.name, "pool_blocks": pool, "step": kind,
                "block_bytes": per_block,
                "argument_bytes": ma.argument_size_in_bytes,
                "output_bytes": ma.output_size_in_bytes,
                "alias_bytes": ma.alias_size_in_bytes,
                "temp_bytes": ma.temp_size_in_bytes,
                "code_bytes": ma.generated_code_size_in_bytes,
                "need_bytes": need,
                "hbm_bytes": 16 * 2 ** 30 * 0.984}), flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
