"""Ahead-of-time compiles of the Pallas kernels for a described TPU v5e.

Interpret-mode parity (tests/test_kernels.py) cannot show that Mosaic, the
TPU kernel compiler, accepts a kernel: block shapes that break its (8, 128)
tiling, vector loads from SMEM and 1-D iotas all pass the interpreter and
are refused on the chip. These tests lower each kernel of the serving path
at the widths the chip runs and compile it for a ``v5e:2x2`` topology that
is described, not attached, so no chip is needed.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and every pytest
worker imports this file.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import cascade_attention as casc
from repro.kernels import flash_attention as fa


@pytest.fixture(scope="module")
def one_chip():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else logs go to /tmp
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compile_text(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


# qwen2.5-3b attention widths (configs/qwen2_5_3b.py) and the D2SD comb
# tree at the production gamma=16, K=4 (launch/steps.py): 16 + 4*15 nodes.
QWEN_HQ, QWEN_HKV, QWEN_D = 16, 2, 128
TREE_N = 16 + 4 * 15


def test_cascade_phase1_paged_compiles_qwen(one_chip):
    b, page, max_pages = 8, 64, 16
    n_pool = b * max_pages + 1
    fn = functools.partial(casc.cascade_phase1_paged, n_splits=8)
    text = _compile_text(
        lambda q, pk, pv, pt, cl, qa: fn(q, pk, pv, pt, cache_len=cl,
                                         q_abs=qa),
        one_chip,
        ((b, QWEN_HQ, TREE_N, QWEN_D), jnp.bfloat16),
        ((n_pool, QWEN_HKV, page, QWEN_D), jnp.bfloat16),
        ((n_pool, QWEN_HKV, page, QWEN_D), jnp.bfloat16),
        ((b, max_pages), jnp.int32),
        ((b,), jnp.int32),
        ((b, TREE_N), jnp.int32))
    assert "tpu_custom_call" in text


def test_cascade_phase1_rolling_compiles_gemma2(one_chip):
    # gemma2-2b local layers: Hq 8, Hkv 4, head_dim 256, window 4096,
    # attention-logit softcap 50, rolling ring buffer of the window.
    b, hq, hkv, d, window = 4, 8, 4, 256, 4096
    fn = functools.partial(casc.cascade_phase1, window=window,
                           attn_softcap=50.0, rolling=True, n_splits=8,
                           bk=512)
    text = _compile_text(
        lambda q, ck, cv, cl, qa: fn(q, ck, cv, cache_len=cl, q_abs=qa),
        one_chip,
        ((b, hq, TREE_N, d), jnp.bfloat16),
        ((b, hkv, window, d), jnp.bfloat16),
        ((b, hkv, window, d), jnp.bfloat16),
        ((b,), jnp.int32),
        ((b, TREE_N), jnp.int32))
    assert "tpu_custom_call" in text


def test_flash_fwd_compiles(one_chip):
    b, t = 2, 512
    text = _compile_text(
        lambda q, k, v: fa.flash_attention_fwd(q, k, v, causal=True),
        one_chip,
        ((b, QWEN_HQ, t, QWEN_D), jnp.bfloat16),
        ((b, QWEN_HKV, t, QWEN_D), jnp.bfloat16),
        ((b, QWEN_HKV, t, QWEN_D), jnp.bfloat16))
    assert "tpu_custom_call" in text


def test_flash_bwd_compiles(one_chip):
    b, t = 1, 256
    qs = ((b, QWEN_HQ, t, QWEN_D), jnp.bfloat16)
    kvs = ((b, QWEN_HKV, t, QWEN_D), jnp.bfloat16)
    text = _compile_text(
        lambda q, k, v, o, lse, do: fa.flash_attention_bwd(
            q, k, v, o, lse, do, causal=True),
        one_chip, qs, kvs, kvs, qs, ((b, QWEN_HQ, t), jnp.float32), qs)
    assert "tpu_custom_call" in text
