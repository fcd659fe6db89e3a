"""Distribution-layer tests on a small in-process host mesh (subprocess: the
main test process keeps 1 device; these spawn `python -c` with
XLA_FLAGS=--xla_force_host_platform_device_count=8)."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

SRC = str(Path(__file__).resolve().parents[1] / "src")


def _run(code: str, devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC
    env.pop("JAX_PLATFORMS", None)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_spdecode_matches_local():
    """KV-sequence-sharded decode attention == single-device reference."""
    _run(r"""
import jax, jax.numpy as jnp, numpy as np
from repro.distributed import sharding as sh
from repro.distributed.spdecode import sharded_cache_attend
from repro.launch.mesh import make_mesh
from repro.models.blocks import _attend_cache_plus_block

mesh = make_mesh(data=2, model=4)
b, tq, hq, hkv, s, d = 2, 6, 4, 2, 64, 16
ks = jax.random.split(jax.random.PRNGKey(0), 5)
q  = jax.random.normal(ks[0], (b, tq, hq, d))
ck = jax.random.normal(ks[1], (b, s, hkv, d))
cv = jax.random.normal(ks[2], (b, s, hkv, d))
bk = jax.random.normal(ks[3], (b, tq, hkv, d))
bv = jax.random.normal(ks[4], (b, tq, hkv, d))
cache_len = jnp.array([50, 30])
q_abs = cache_len[:, None] + jnp.arange(tq)[None, :]
mask = jnp.tril(jnp.ones((tq, tq), bool))

kk = jnp.concatenate([ck, bk], 1)
vv = jnp.concatenate([cv, bv], 1)
o2 = _attend_cache_plus_block(q, kk, vv, cache_cap=s, cache_len=cache_len,
                              q_abs=q_abs, window=None, extra_mask=mask,
                              attn_softcap=None, impl='dense', kv_chunk=64,
                              rolling=False)
with sh.use_sharding(mesh, dict(sh.LOGICAL_RULES, kv_seq="model")):
    # exact with fp32 merge payload
    o1 = jax.jit(lambda *a: sharded_cache_attend(
        *a, cache_len=cache_len, q_abs=q_abs, window=None,
        attn_softcap=None, blk_mask=mask, rolling=False,
        merge_dtype=jnp.float32))(q, ck, cv, bk, bv)
    np.testing.assert_allclose(np.asarray(o1, np.float32),
                               np.asarray(o2, np.float32),
                               rtol=2e-5, atol=2e-5)
    # bf16 merge payload (the production default) within bf16 tolerance
    o3 = jax.jit(lambda *a: sharded_cache_attend(
        *a, cache_len=cache_len, q_abs=q_abs, window=None,
        attn_softcap=None, blk_mask=mask, rolling=False))(q, ck, cv, bk, bv)
    np.testing.assert_allclose(np.asarray(o3, np.float32),
                               np.asarray(o2, np.float32),
                               rtol=2e-2, atol=2e-2)
print('OK')
""")


def test_sharded_train_step_matches_single_device():
    """pjit'd train step on a 2x4 mesh == single-device step (same math)."""
    _run(r"""
import jax, jax.numpy as jnp, numpy as np
from repro.config.base import ModelConfig, OptimizerConfig
from repro.distributed import sharding as sh
from repro.launch.mesh import make_mesh
from repro.models import lm
from repro.optim import optimizers as opt_lib

cfg = ModelConfig(num_layers=2, d_model=64, num_heads=4, num_kv_heads=2,
                  d_ff=128, vocab_size=512, max_seq_len=64, remat=False,
                  dtype='float32')
hp = OptimizerConfig(lr=1e-3, total_steps=10)
params = lm.lm_init(jax.random.PRNGKey(0), cfg)
opt_init, opt_update = opt_lib.make_optimizer(hp)
opt = opt_init(params)
toks = jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0, 512)
batch = {'tokens': toks, 'labels': jnp.roll(toks, -1, 1),
         'mask': jnp.ones((8, 32), jnp.float32)}

def step(params, opt, batch):
    from repro.distributed.sharding import constrain_params
    params = constrain_params(params)
    loss, g = jax.value_and_grad(lambda p: lm.loss_fn(p, batch, cfg))(params)
    p2, o2, _ = opt_update(g, opt, params)
    return p2, loss

p_ref, l_ref = step(params, opt, batch)

mesh = make_mesh(data=2, model=4)
with sh.use_sharding(mesh, sh.LOGICAL_RULES, fsdp=True):
    shard_in = (sh.params_shardings(params, mesh),
                sh.params_shardings(opt, mesh),
                sh.params_shardings(batch, mesh))
    p_sh, l_sh = jax.jit(step, in_shardings=shard_in)(params, opt, batch)

assert abs(float(l_ref) - float(l_sh)) < 1e-4, (l_ref, l_sh)
d = max(float(jnp.abs(a - jax.device_get(b)).max())
        for a, b in zip(jax.tree.leaves(p_ref), jax.tree.leaves(p_sh)))
assert d < 1e-4, d
print('OK')
""")


def test_elastic_checkpoint_restore_across_meshes(tmp_path):
    """Save on a 2x4 mesh, restore onto 1x2 (elastic scale-down)."""
    _run(rf"""
import jax, jax.numpy as jnp, numpy as np
from repro.checkpoint.checkpointer import Checkpointer
from repro.config.base import ModelConfig
from repro.distributed import sharding as sh
from repro.launch.mesh import make_mesh
from repro.models import lm

cfg = ModelConfig(num_layers=2, d_model=64, num_heads=4, num_kv_heads=2,
                  d_ff=128, vocab_size=512, max_seq_len=64, remat=False)
params = lm.lm_init(jax.random.PRNGKey(0), cfg)
mesh_a = make_mesh(data=2, model=4)
with sh.use_sharding(mesh_a, sh.LOGICAL_RULES):
    sharded = jax.device_put(params, sh.params_shardings(params, mesh_a))
ck = Checkpointer(r'{tmp_path}')
ck.save(1, sharded)
mesh_b = make_mesh(data=1, model=2)
with sh.use_sharding(mesh_b, sh.LOGICAL_RULES):
    restored, _ = ck.restore(params, mesh=mesh_b)
for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(restored)):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
# restored leaves actually live on mesh_b
leaf = jax.tree.leaves(restored)[0]
assert leaf.sharding.mesh.shape == {{'data': 1, 'model': 2}}, leaf.sharding
print('OK')
""")


def test_moe_scatter_sharded_matches_local():
    _run(r"""
import jax, jax.numpy as jnp, numpy as np
from repro.config.base import ModelConfig, MoEConfig
from repro.distributed import sharding as sh
from repro.launch.mesh import make_mesh
from repro.models import moe as moe_lib

cfg = ModelConfig(num_layers=1, d_model=64, num_heads=4, num_kv_heads=2,
                  d_ff=128, vocab_size=97, dtype='float32',
                  moe=MoEConfig(num_experts=8, top_k=2, capacity_factor=2.0,
                                dispatch='scatter'))
p = moe_lib.moe_init(jax.random.PRNGKey(0), cfg)
x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, 64))
y_ref = moe_lib.moe_apply(p, x, cfg)
mesh = make_mesh(data=2, model=4)
with sh.use_sharding(mesh, sh.LOGICAL_RULES):
    y_sh = jax.jit(lambda p, x: moe_lib.moe_apply(p, x, cfg),
                   in_shardings=(sh.params_shardings(p, mesh),
                                 sh.params_shardings(x, mesh)))(p, x)
np.testing.assert_allclose(np.asarray(y_ref), np.asarray(jax.device_get(y_sh)),
                           rtol=2e-4, atol=2e-4)
print('OK')
""")


def test_pipeline_parallel_matches_sequential():
    """GPipe pod-axis pipeline == sequential stage application."""
    _run(r"""
import jax, jax.numpy as jnp, numpy as np
from repro.distributed import sharding as sh
from repro.distributed.pipeline_parallel import pipeline_apply

from repro.launch.mesh import mesh_of
mesh = mesh_of((4, 2), ("pod", "data"))
S, M, mb, d = 4, 6, 3, 16
ks = jax.random.split(jax.random.PRNGKey(0), 2)
w = jax.random.normal(ks[0], (S, d, d)) * 0.3
xs = jax.random.normal(ks[1], (M, mb, d))

def stage(wi, x):
    return jnp.tanh(x @ wi["w"])

ref = xs
for s in range(S):
    ref = jnp.tanh(ref @ w[s])

with sh.use_sharding(mesh, sh.LOGICAL_RULES):
    out = jax.jit(lambda w, xs: pipeline_apply(stage, {"w": w}, xs))(w, xs)
np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5,
                           atol=1e-5)
print('OK')
""")


def test_compressed_grad_allreduce_error_feedback():
    """int8+EF gradient all-reduce: mean within quant tolerance and the EF
    residual shrinks the bias across steps."""
    _run(r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.distributed.collectives import (compressed_grad_allreduce,
                                           init_error_state)

from repro.launch.mesh import mesh_of
mesh = mesh_of((8,), ("data",))
g_global = jax.random.normal(jax.random.PRNGKey(0), (8, 1000)) \
    * jnp.logspace(-3, 0, 1000)[None]
true_mean = g_global.mean(0)

def step(g_shard, e):
    return compressed_grad_allreduce({"g": g_shard}, {"g": e}, axis="data")

f = jax.jit(jax.shard_map(step, mesh=mesh, in_specs=(P("data"), P("data")),
                      out_specs=(P("data"), P("data")),
                      check_vma=False))
e = jnp.zeros((8, 1000))
mean, e2 = f(g_global, e)
got = np.asarray(mean["g"])[0]
rel = np.abs(got - np.asarray(true_mean)).max() / np.abs(true_mean).max()
assert rel < 0.02, rel
# error feedback: residual is bounded by one quantization step
assert float(jnp.abs(e2["g"]).max()) < float(jnp.abs(g_global).max()) / 100
print('OK')
""")
