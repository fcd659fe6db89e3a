"""Sharded resident serving: ONE engine spanning a host mesh.

Subprocess tests (the main pytest process keeps 1 CPU device; these
spawn ``python -c`` under ``XLA_FLAGS=--xla_force_host_platform_
device_count=8``) asserting the tentpole invariant — *page identity is
global, page bytes are per-shard* — end to end:

* a mixed serving workload (varying prompt lengths and budgets, radix
  prefix cache on, cross-wave hits through the engine-lifetime pool) is
  per-request TOKEN-IDENTICAL between a ``kv_seq``-sharded engine and
  the single-device engine, with both engines living in ONE process
  (exercising the ``mesh_tag`` static jit-cache split);
* the seed-0 chunk of the randomized pool/radix/COW invariant suite
  passes unchanged against the per-shard pool (``REPRO_MESH`` re-runs
  it inside a ``use_sharding`` context — the host allocator, refcounts
  and radix tree never see the mesh, so every invariant must hold
  verbatim).
"""
import os
import subprocess
import sys
from pathlib import Path

SRC = str(Path(__file__).resolve().parents[1] / "src")
TESTS = str(Path(__file__).resolve().parent)


def _run(code: str, devices: int = 8, extra_env=None) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC + os.pathsep + TESTS
    env.pop("JAX_PLATFORMS", None)
    env.pop("REPRO_MESH", None)
    if extra_env:
        env.update(extra_env)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, (out.stdout[-2000:], out.stderr[-3000:])
    return out.stdout


def test_sharded_engine_token_parity_and_cross_wave_hits():
    """Sharded-vs-single-device ServingEngine parity on a mixed resident
    workload: two submission phases with a wave turnover between them,
    phase-2 prompts extending phase-1 strings so the radix hits cross the
    turnover THROUGH the kv_seq-sharded engine pool."""
    _run(r"""
import contextlib
import numpy as np, jax
from conftest import tiny_target, tiny_drafter
from repro.config.base import SpecConfig
from repro.core import pipeline as pl
from repro.core.drafter import drafter_init
from repro.distributed import sharding as sh
from repro.launch.mesh import make_mesh
from repro.models import lm
from repro.serving.engine import ServingEngine

assert jax.device_count() == 8, jax.device_count()
VOCAB, GAMMA = 61, 4
tcfg = tiny_target(vocab=VOCAB, dtype="float32")
dcfg = tiny_drafter(vocab=VOCAB, gamma=GAMMA, dtype="float32",
                    target_cfg=tcfg)
tp = lm.lm_init(jax.random.PRNGKey(0), tcfg)
d1 = drafter_init(jax.random.PRNGKey(1), dcfg)
d2 = drafter_init(jax.random.PRNGKey(2), dcfg)
spec = SpecConfig(gamma=GAMMA, top_k_branches=2, mode="d2sd")
bundle = pl.SpecBundle(tcfg, dcfg, dcfg, spec, tp, d1, d2)

rng = np.random.default_rng(0)
sysp = rng.integers(3, VOCAB, size=11).astype(np.int32)
phase1 = []
for i in range(4):
    tail = rng.integers(3, VOCAB, size=3 + 2 * i).astype(np.int32)
    phase1.append((np.concatenate([sysp, tail]), 3 + (i % 3)))
# phase 2 re-sends phase-1 prompts (hits must cover prompt + committed
# tokens) plus fresh mixed-length cold prompts
phase2 = [(p, n) for p, n in phase1[:2]]
for i in range(2):
    phase2.append((rng.integers(3, VOCAB, size=6 + 5 * i).astype(np.int32),
                   4))

def serve(mesh):
    ctx = (sh.use_sharding(mesh, dict(sh.LOGICAL_RULES, kv_seq="model"))
           if mesh is not None else contextlib.nullcontext())
    with ctx:
        eng = ServingEngine(bundle, batch_size=2, seed=0,
                            cache_impl="paged", page_size=8,
                            prefix_cache=True, pool_scope="engine")
    for p, n in phase1:
        eng.submit(p, max_new=n)
    eng.run()                       # wave(s) 1: seeds the radix tree
    hits0 = eng.stats["prefix_hit_tokens"]
    waves0 = eng.stats["waves"]
    for p, n in phase2:
        eng.submit(p, max_new=n)
    stats = eng.run()               # new wave over the SAME engine pool
    assert stats["waves"] > waves0
    outs = {r.uid: r.out.tolist() for r in eng.done}
    return outs, stats, stats["prefix_hit_tokens"] - hits0

o_ref, s_ref, _ = serve(None)
o_sh, s_sh, hits_across = serve(make_mesh(data=2, model=4))
assert o_sh == o_ref, {u: (o_sh.get(u), o_ref.get(u)) for u in o_ref
                       if o_sh.get(u) != o_ref[u]}
assert s_sh["kv_shards"] == 4, s_sh["kv_shards"]
assert s_sh["pool_shard_slots"] * 4 == s_sh["pool_pages"] * 8, s_sh
# radix hits crossed the wave turnover through the sharded pool
assert hits_across > 0, s_sh
assert s_sh["decode_collective_bytes"] > 0, s_sh
# single-device engine in the same process stayed mesh-free
assert s_ref["kv_shards"] == 1 and s_ref["decode_collective_bytes"] == 0
print("parity ok")
""")


def test_sharded_pallas_read_path_token_parity():
    """attn_impl="pallas" vs "gather" on the kv_seq-sharded paged engine:
    the cascade kernel runs on each shard's local pool slice inside
    shard_map (pos_stride = global page, pos_offset = shard * page_loc,
    LSE psum merge across shards) and per-request tokens must be
    identical to the gather read path on the same mesh."""
    _run(r"""
import numpy as np, jax
from conftest import tiny_target, tiny_drafter
from repro.config.base import SpecConfig
from repro.core import pipeline as pl
from repro.core.drafter import drafter_init
from repro.distributed import sharding as sh
from repro.launch.mesh import make_mesh
from repro.models import lm
from repro.serving.engine import ServingEngine

assert jax.device_count() == 8, jax.device_count()
VOCAB, GAMMA = 61, 4
tcfg = tiny_target(vocab=VOCAB, dtype="float32")
dcfg = tiny_drafter(vocab=VOCAB, gamma=GAMMA, dtype="float32",
                    target_cfg=tcfg)
tp = lm.lm_init(jax.random.PRNGKey(0), tcfg)
d1 = drafter_init(jax.random.PRNGKey(1), dcfg)
d2 = drafter_init(jax.random.PRNGKey(2), dcfg)
spec = SpecConfig(gamma=GAMMA, top_k_branches=2, mode="d2sd")
bundle = pl.SpecBundle(tcfg, dcfg, dcfg, spec, tp, d1, d2)

rng = np.random.default_rng(1)
reqs = [(rng.integers(3, VOCAB, size=p).astype(np.int32), n)
        for p, n in [(11, 4), (5, 3), (8, 5), (6, 3)]]

mesh = make_mesh(data=2, model=4)
outs = {}
for impl in ("gather", "pallas"):
    with sh.use_sharding(mesh, dict(sh.LOGICAL_RULES, kv_seq="model")):
        eng = ServingEngine(pl.with_attn_impl(bundle, impl),
                            batch_size=2, seed=0, cache_impl="paged",
                            page_size=8)
    for p, n in reqs:
        eng.submit(p, max_new=n)
    stats = eng.run()
    assert stats["kv_shards"] == 4, stats["kv_shards"]
    outs[impl] = {r.uid: r.out.tolist() for r in eng.done}
assert outs["pallas"] == outs["gather"], {
    u: (outs["pallas"].get(u), outs["gather"].get(u))
    for u in outs["gather"] if outs["pallas"].get(u) != outs["gather"][u]}
print("sharded pallas parity ok")
""")


def test_sharded_drafter_read_parity():
    """Drafter feature-cache reads under a kv_seq mesh go through the
    SAME shard_map hook as the verify read (ROADMAP item d closed): a
    paged ``drafter_forward`` on a 4-way kv_seq mesh must produce logits
    identical to the meshless gather path, for both read_impls, and the
    shard_map hook must actually engage (its LSE-psum payload shows up
    in PAYLOAD_TRACE)."""
    _run(r"""
import numpy as np, jax
import jax.numpy as jnp
from conftest import tiny_target, tiny_drafter
from repro.core import drafter as dr
from repro.distributed import sharding as sh
from repro.distributed import spdecode as sp
from repro.launch.mesh import make_mesh

assert jax.device_count() == 8, jax.device_count()
VOCAB, GAMMA = 61, 4
tcfg = tiny_target(vocab=VOCAB, dtype="float32")
dcfg = tiny_drafter(vocab=VOCAB, gamma=GAMMA, dtype="float32",
                    target_cfg=tcfg)
p = dr.drafter_init(jax.random.PRNGKey(1), dcfg)

B, PAGE, MP = 2, 8, 6
cache = dr.init_feat_cache(dcfg, B, PAGE * MP, dtype=jnp.float32,
                           cache_impl="paged", page_size=PAGE)
rng = np.random.default_rng(0)
feats = jnp.asarray(rng.standard_normal(
    (B, 13, dcfg.target_feature_dim)), jnp.float32)
pos = jnp.broadcast_to(jnp.arange(13)[None], (B, 13))
n_new = jnp.array([13, 9])                  # ragged, page-straddling
cache = dr.extend_feat_cache(p, dcfg, cache, feats, pos, n_new)
blk = dr.dflash_block(jnp.array([5, 7]), GAMMA, dcfg.mask_token)

ref = np.asarray(dr.drafter_forward(p, dcfg, blk, cache))
mesh = make_mesh(data=2, model=4)
for impl in ("gather", "pallas"):
    dci = __import__("dataclasses").replace(dcfg, attn_impl=impl)
    with sh.use_sharding(mesh, dict(sh.LOGICAL_RULES, kv_seq="model")):
        sp.PAYLOAD_TRACE.clear()
        out = np.asarray(dr.drafter_forward(p, dci, blk, cache))
        assert len(sp.PAYLOAD_TRACE) == dcfg.num_layers, (
            impl, len(sp.PAYLOAD_TRACE))   # shard_map hook engaged
    np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-4)
print("sharded drafter read parity ok")
""")


def test_engine_replicates_weights_onto_its_mesh():
    """An engine built under ``serve.mesh_context`` holds every weight
    replicated on all mesh devices, with the caller's device-0 buffer as
    device 0's copy (no second copy there) and the caller's arrays still
    usable; an engine built without a mesh keeps the bundle as given."""
    _run(r"""
import jax, numpy as np
from conftest import tiny_target, tiny_drafter
from repro.config.base import SpecConfig
from repro.launch.serve import mesh_context, random_bundle
from repro.serving.engine import ServingEngine

assert jax.device_count() == 4, jax.device_count()
tcfg = tiny_target(vocab=61)
dcfg = tiny_drafter(vocab=61, gamma=4, target_cfg=tcfg)
bundle = random_bundle(tcfg, dcfg, SpecConfig(gamma=4, top_k_branches=2))
assert ServingEngine(bundle, cache_impl="paged", page_size=8).bundle \
    is bundle
with mesh_context(1, 4):
    eng = ServingEngine(bundle, cache_impl="paged", page_size=8)
dev0 = jax.devices()[0]
for mine, theirs in zip(jax.tree.leaves(eng.bundle),
                        jax.tree.leaves(bundle)):
    assert len(mine.devices()) == 4, mine.sharding
    assert mine.sharding.is_fully_replicated
    (home,) = [s for s in mine.addressable_shards if s.device == dev0]
    assert (home.data.unsafe_buffer_pointer()
            == theirs.unsafe_buffer_pointer())
    np.testing.assert_array_equal(np.asarray(mine), np.asarray(theirs))
with mesh_context(1, 4):   # already on the mesh: left as it is
    again = ServingEngine(eng.bundle, cache_impl="paged", page_size=8)
assert all(a is b for a, b in zip(jax.tree.leaves(again.bundle),
                                  jax.tree.leaves(eng.bundle)))
print("weights replicated ok")
""", devices=4)


def test_pool_invariants_seed0_under_mesh():
    """The tier-1 (seed-0) chunk of the pool/radix/COW invariant suite,
    re-run with every test wrapped in a 1x4 kv_seq mesh context via the
    REPRO_MESH conftest fixture: page identity is host-global, so the
    refcount / free-list / COW bit-freeze invariants must hold verbatim
    over the per-shard pool."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC
    env["REPRO_MESH"] = "1x4"
    env.pop("JAX_PLATFORMS", None)
    out = subprocess.run(
        [sys.executable, "-m", "pytest", "-x", "-q", "-m", "not slow",
         "-p", "no:cacheprovider",
         str(Path(TESTS) / "test_pool_invariants.py"),
         "-k", "randomized_pool_invariants or cached_pages_survive"],
        env=env, capture_output=True, text=True, timeout=900,
        cwd=str(Path(TESTS).parent))
    assert out.returncode == 0, (out.stdout[-3000:], out.stderr[-2000:])
