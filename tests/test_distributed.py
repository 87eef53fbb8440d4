"""Distribution-layer tests.

Sharding-rule units run in-process (no devices needed); everything needing
multiple devices runs in a subprocess with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` so the main pytest
process keeps seeing exactly one device.
"""

import numpy as np
import pytest

from conftest import run_subprocess_devices


# ------------------------------------------------------- rule units (1 dev)

def test_logical_to_spec_divisibility_fallback():
    import jax
    from jax.sharding import PartitionSpec
    from repro.distributed.sharding import logical_to_spec
    mesh = jax.make_mesh((1,), ("model",))   # single device is fine
    log = []
    spec = logical_to_spec(("heads", None), (24, 4), mesh, None, log)
    assert spec == PartitionSpec("model", None)  # 24 % 1 == 0
    # fake a 16-wide axis via rules on a 1-dev mesh isn't possible; the
    # real 16-way behaviour is covered by the dry-run fallback logs.


def test_unknown_logical_axis_raises():
    import jax
    from repro.distributed.sharding import logical_to_spec
    mesh = jax.make_mesh((1,), ("model",))
    with pytest.raises(KeyError):
        logical_to_spec(("not_an_axis",), (8,), mesh)


def test_lsc_is_identity_without_mesh():
    import jax.numpy as jnp
    from repro.distributed.sharding import lsc
    x = jnp.ones((4, 4))
    assert lsc(x, "batch", "embed") is x


# --------------------------------------------------- multi-device (subproc)

def test_compressed_psum_exact_and_error_feedback():
    run_subprocess_devices("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.distributed.compression import compressed_psum

mesh = jax.make_mesh((8,), ("data",))
f = jax.shard_map(lambda g, e: compressed_psum({"w": g}, {"w": e}, "data"),
                  mesh=mesh, in_specs=(P("data", None), P("data", None)),
                  out_specs=({"w": P(None, None)}, {"w": P("data", None)}),
                  check_vma=False)
g = jax.random.normal(jax.random.PRNGKey(0), (8, 128))
exact = jnp.mean(g, axis=0)
e = jnp.zeros((8, 128))
synced, eo = f(g, e)
err1 = float(jnp.max(jnp.abs(synced["w"][0] - exact)))
assert err1 < 0.05, err1

# error feedback: simulate SGD where compression error is carried —
# the AVERAGE of compressed steps converges to the average of exact steps
w_c = jnp.zeros((128,)); w_x = jnp.zeros((128,)); e = jnp.zeros((8, 128))
for i in range(40):
    gi = g + 0.01 * jax.random.normal(jax.random.PRNGKey(i), g.shape)
    synced, eo = f(gi, e); e = eo["w"]
    w_c = w_c - 0.1 * synced["w"][0]
    w_x = w_x - 0.1 * jnp.mean(gi, axis=0)
drift = float(jnp.max(jnp.abs(w_c - w_x)))
assert drift < 0.02, drift
print("OK", err1, drift)
""")


def test_context_parallel_socket_attend():
    run_subprocess_devices("""
import jax, jax.numpy as jnp, numpy as np
from repro.distributed.context_parallel import context_parallel_socket_attend
from repro.core import socket, hashing

mesh = jax.make_mesh((8,), ("data",))
cfg = socket.SocketConfig(num_planes=8, num_tables=24, tau=0.4,
                          sparsity=4.0, sink_tokens=8, window_tokens=8,
                          min_k=16)
d, n, B, KVH, G = 32, 1024, 1, 2, 2
rng = jax.random.PRNGKey(1)
kk, kv, kq, kw = jax.random.split(rng, 4)
w = hashing.make_hash_params(kw, d, 8, 24)
keys = jax.random.normal(kk, (B,KVH,n,d))
vals = jax.random.normal(kv, (B,KVH,n,d))
side = socket.precompute_key_hashes(cfg, w, keys, vals)
q = 2.0*keys[:,:,500][:, :, None, None, :] + 0.1*jax.random.normal(kq,(B,KVH,G,1,d))
out = context_parallel_socket_attend(cfg, mesh, ("data",), w, q, keys,
                                     vals, side.bits,
                                     side.vnorm.astype(jnp.float32),
                                     length=900, scale=1/np.sqrt(d))
ref = socket.socket_attend(cfg, w, q, keys, vals, side, length=900,
                           scale=1/np.sqrt(d))
rel = float(jnp.linalg.norm(out-ref)/jnp.linalg.norm(ref))
assert rel < 0.08, rel
assert out.shape == ref.shape
print("OK", rel)
""")


def test_gpipe_forward_matches_sequential():
    run_subprocess_devices("""
import jax, jax.numpy as jnp, numpy as np
from repro.distributed.pipeline import gpipe_forward

mesh = jax.make_mesh((4,), ("stage",))
stages, layers_per, d = 4, 2, 16
rng = jax.random.PRNGKey(0)
ws = jax.random.normal(rng, (stages, layers_per, d, d)) * 0.2

def stage_fn(params, x):
    for i in range(layers_per):
        x = jnp.tanh(x @ params[i])
    return x

x = jax.random.normal(jax.random.fold_in(rng, 1), (8, d))
out = gpipe_forward(mesh, "stage", stage_fn, ws, x, num_micro=4)

ref = x
for s in range(stages):
    ref = stage_fn(ws[s], ref)
np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)
print("OK")
""")


def test_pjit_train_step_multi_device():
    """End-to-end sharded train step on a (4, 2) mesh with FSDP+TP rules."""
    run_subprocess_devices("""
import jax, jax.numpy as jnp
from repro.configs import get_config
from repro.distributed import sharding as shd
from repro.launch import specs as sp
from repro.optim import AdamWConfig, init_adamw
from repro.runtime.steps import make_train_step
from repro.launch.mesh import make_test_mesh
from repro.models import param as pm, transformer as tfm

mesh = make_test_mesh(4, 2)
cfg = get_config("minitron-8b").smoke().replace(num_groups=1)
ocfg = AdamWConfig()
rules = {}
with shd.activate_mesh(mesh, rules):
    params_sds, params_sh = sp.param_specs(cfg, mesh, rules, [])
    opt_sds, opt_sh = sp.opt_specs(ocfg, params_sds, params_sh, mesh,
                                   rules, [])
    params = pm.unbox(tfm.init_model(cfg, jax.random.PRNGKey(0)))
    params = jax.tree_util.tree_map(jax.device_put, params, params_sh)
    opt = init_adamw(ocfg, params)
    opt = jax.tree_util.tree_map(jax.device_put, opt, opt_sh)
    step = jax.jit(make_train_step(cfg, ocfg, accum=2,
                                   grad_shardings=params_sh),
                   in_shardings=(params_sh, opt_sh, None),
                   out_shardings=(params_sh, opt_sh, None),
                   donate_argnums=(0, 1))
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (4, 64),
                                          0, cfg.vocab_size),
             "labels": jax.random.randint(jax.random.PRNGKey(2), (4, 64),
                                          0, cfg.vocab_size)}
    p2, o2, m = step(params, opt, batch)
assert jnp.isfinite(m["loss"])
print("OK", float(m["loss"]))
""")


def test_elastic_trainer_shrinks_mesh():
    """Trainer loses devices mid-run, rebuilds a smaller mesh, resumes
    from checkpoint and finishes."""
    run_subprocess_devices("""
import jax, numpy as np, tempfile
from jax.sharding import Mesh
from repro.configs import get_config
from repro.data import DataConfig
from repro.optim import AdamWConfig
from repro.optim.schedule import ScheduleConfig
from repro.runtime.fault_tolerance import FailureInjector
from repro.runtime.train_loop import Trainer, TrainLoopConfig

def mesh_factory(devices):
    n = len(devices)
    # largest power-of-two data axis
    while n & (n - 1):
        n -= 1
    return Mesh(np.asarray(devices[:n]).reshape(n, 1), ("data", "model"))

cfg = get_config("minitron-8b").smoke().replace(num_groups=1,
                                                attention_backend="dense")
ocfg = AdamWConfig(schedule=ScheduleConfig(peak_lr=1e-3, warmup_steps=2,
                                           decay_steps=12))
loop = TrainLoopConfig(total_steps=12, checkpoint_every=4)
data = DataConfig(seq_len=32, global_batch=8, vocab_size=cfg.vocab_size)
inj = FailureInjector(schedule={6: "lose_device:4"})
with tempfile.TemporaryDirectory() as d:
    tr = Trainer(cfg, ocfg, loop, data, d, mesh_factory=mesh_factory,
                 injector=inj)
    assert tr.mesh.devices.size == 8
    log = tr.run()
    assert tr.rebuild_count == 1
    assert tr.mesh.devices.size == 4, tr.mesh.devices.size
    assert tr.step == 12
print("OK elastic: 8 -> 4 devices")
""", devices=8, timeout=900)


def test_alltoall_moe_matches_global_and_differentiates():
    """The shard_map EP dispatch must be bit-exact vs global dispatch
    (matched dropless capacity) and give matching gradients."""
    run_subprocess_devices("""
import jax, jax.numpy as jnp, numpy as np
from repro.configs.base import ModelConfig
from repro.distributed import sharding as shd
from repro.models import moe as moe_mod, param as pm

mesh = jax.make_mesh((2, 4), ("data", "model"))
cfg = ModelConfig(name="t", family="moe", d_model=32, d_ff=64,
                  num_experts=8, num_experts_per_tok=2,
                  capacity_factor=8.0, mlp_activation="swiglu",
                  moe_dispatch="alltoall")
params = pm.unbox(moe_mod.init_moe(cfg, jax.random.PRNGKey(0)))
x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, 32))

y_ref, _ = moe_mod.apply_moe(cfg.replace(moe_dispatch="global"), params, x)
with shd.activate_mesh(mesh):
    y_a2a, _ = jax.jit(lambda p, xx: moe_mod.apply_moe(cfg, p, xx))(params, x)
np.testing.assert_allclose(np.asarray(y_ref), np.asarray(y_a2a), atol=1e-5)

def loss_g(p):
    y, _ = moe_mod.apply_moe(cfg.replace(moe_dispatch="global"), p, x)
    return jnp.sum(y ** 2)

def loss_a(p):
    y, _ = moe_mod.apply_moe(cfg, p, x)
    return jnp.sum(y ** 2)

g_ref = jax.grad(loss_g)(params)
with shd.activate_mesh(mesh):
    g_a2a = jax.jit(jax.grad(loss_a))(params)
for k in ("w_gate", "w_up", "w_down"):
    np.testing.assert_allclose(np.asarray(g_ref[k]), np.asarray(g_a2a[k]),
                               atol=2e-4)
print("OK a2a forward+grads exact")
""")


def test_context_parallel_pooled_selection():
    run_subprocess_devices("""
import jax, jax.numpy as jnp, numpy as np
from repro.distributed.context_parallel import context_parallel_socket_attend
from repro.core import socket, hashing
from repro.baselines import oracle

mesh = jax.make_mesh((4,), ("data",))
cfg = socket.SocketConfig(num_planes=8, num_tables=24, tau=0.4,
                          sparsity=4.0, sink_tokens=8, window_tokens=8,
                          min_k=16, selection="pooled")
d, n, B, KVH, G = 32, 512, 1, 2, 2
rng = jax.random.PRNGKey(2)
kk, kv, kq, kw = jax.random.split(rng, 4)
w = hashing.make_hash_params(kw, d, 8, 24)
keys = jax.random.normal(kk, (B,KVH,n,d))
vals = jax.random.normal(kv, (B,KVH,n,d))
side = socket.precompute_key_hashes(cfg, w, keys, vals)
q = 3.0*keys[:,:,300][:, :, None, None, :] + 0.1*jax.random.normal(kq,(B,KVH,G,1,d))
out = context_parallel_socket_attend(cfg, mesh, ("data",), w, q, keys,
                                     vals, side.bits,
                                     side.vnorm.astype(jnp.float32),
                                     length=480, scale=1/np.sqrt(d))
dense = oracle.dense_attention(q, keys, vals, scale=1/np.sqrt(d), length=480)
rel = float(jnp.linalg.norm(out-dense)/jnp.linalg.norm(dense))
assert rel < 0.08, rel
print("OK pooled cp", rel)
""")
