"""Model substrate: every block family, decode-vs-full consistency, MoE
dispatch equivalence, RWKV chunked-vs-scan, attention impl equivalence."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # container lacks hypothesis; see _hypo_shim
    from _hypo_shim import given, settings, strategies as st

from repro.config.base import ModelConfig, MoEConfig
from repro.models import lm, rwkv
from repro.models.attention import attend

BASE = dict(d_model=64, num_heads=4, num_kv_heads=2, d_ff=128, vocab_size=97,
            max_seq_len=64, remat=False)


def _consistency(cfg, ctx_dim=0, t=12, tol=0.15):
    params = lm.lm_init(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, t), 0,
                              cfg.vocab_size)
    ctx = (jax.random.normal(jax.random.PRNGKey(2), (2, 5, cfg.d_model),
                             jnp.bfloat16) if ctx_dim else None)
    out = lm.forward(params, toks, cfg, ctx=ctx)
    assert not jnp.isnan(out["logits"].astype(jnp.float32)).any()
    states = lm.init_states(cfg, 2, 32, ctx_len=5)
    o1 = lm.forward(params, toks[:, :-1], cfg, states=states, write_kv=True,
                    ctx=ctx)
    o2 = lm.forward(params, toks[:, -1:], cfg, states=o1["states"],
                    write_kv=False)
    d = jnp.abs(o2["logits"][:, -1].astype(jnp.float32)
                - out["logits"][:, -1].astype(jnp.float32)).max()
    assert d < tol, d


def test_dense_gqa():
    _consistency(ModelConfig(num_layers=4, qkv_bias=True, qk_norm=True,
                             **BASE))


def test_gemma2_like():
    _consistency(ModelConfig(num_layers=4, layer_pattern=("local", "global"),
                             sliding_window=8, use_post_norm=True,
                             attn_softcap=50.0, logit_softcap=30.0, **BASE))


def test_hybrid_tail():
    _consistency(ModelConfig(num_layers=5,
                             layer_pattern=("recurrent", "recurrent", "local"),
                             sliding_window=8, **BASE))


def test_rwkv_stack():
    _consistency(ModelConfig(num_layers=4, layer_pattern=("rwkv",),
                             rwkv_head_dim=16, **BASE))


def test_cross_attention():
    _consistency(ModelConfig(num_layers=4, cross_attn_every=2, **BASE),
                 ctx_dim=1)


@pytest.mark.parametrize("dispatch", ["einsum", "scatter"])
def test_moe(dispatch):
    # generous capacity: decode-vs-full consistency requires no drops
    _consistency(ModelConfig(
        num_layers=4, moe=MoEConfig(num_experts=4, top_k=2,
                                    capacity_factor=4.0, dispatch=dispatch),
        **BASE), tol=0.16)


def test_moe_dispatch_paths_agree():
    from repro.models import moe as moe_lib
    cfg = ModelConfig(num_layers=1, moe=MoEConfig(
        num_experts=4, top_k=2, capacity_factor=4.0), dtype="float32", **BASE)
    p = moe_lib.moe_init(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model))
    y1 = moe_lib.moe_apply(p, x, cfg, dispatch="einsum")
    y2 = moe_lib.moe_apply(p, x, cfg, dispatch="scatter")
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               rtol=2e-4, atol=2e-4)


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 10 ** 6))
def test_rwkv_chunked_equals_scan(seed):
    b, t, h, dh = 2, 64, 2, 16
    key = jax.random.PRNGKey(seed)
    ks = jax.random.split(key, 5)
    r = jax.random.normal(ks[0], (b, t, h, dh))
    k = jax.random.normal(ks[1], (b, t, h, dh))
    v = jax.random.normal(ks[2], (b, t, h, dh))
    # decay from the parameterization w = exp(-exp(x)) in the regime the
    # fp32 factorization supports (per-chunk cumulative decay < ~35 nats;
    # see time_mix_chunked docstring — the scan path covers the rest).
    w = jnp.exp(-jnp.exp(jax.random.normal(ks[3], (b, t, h, dh)) * 0.5 - 2.0))
    u = jax.random.normal(ks[4], (h, dh)) * 0.5
    s0 = jnp.zeros((b, h, dh, dh))
    o1, s1 = rwkv._time_mix_scan(r, k, v, w, u, s0)
    o2, s2 = rwkv.time_mix_chunked(r, k, v, w, u, s0, chunk=16)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), rtol=2e-3,
                               atol=2e-3)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), rtol=2e-3,
                               atol=2e-3)


@settings(max_examples=10, deadline=None)
@given(st.integers(1, 4), st.integers(1, 3), st.booleans())
def test_attention_impls_agree(hq_mult, seed, causal):
    hkv = 2
    hq = hkv * hq_mult
    b, tq, tkv, dh = 2, 8, 24, 16
    key = jax.random.PRNGKey(seed)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (b, tq, hq, dh))
    k = jax.random.normal(ks[1], (b, tkv, hkv, dh))
    v = jax.random.normal(ks[2], (b, tkv, hkv, dh))
    kwargs = dict(causal=causal, q_offset=tkv - tq, window=None, kv_len=20)
    y1 = attend(q, k, v, impl="dense", **kwargs)
    y2 = attend(q, k, v, impl="chunked", kv_chunk=7, **kwargs)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), rtol=2e-5,
                               atol=2e-5)


def test_snap_at_state_advance():
    """Replay with snap_at=n must equal stepping n tokens."""
    cfg = ModelConfig(num_layers=3, layer_pattern=("rwkv",), rwkv_head_dim=16,
                      dtype="float32", **BASE)
    p = lm.lm_init(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, 97)
    n_keep = jnp.array([3, 5])
    s0 = lm.init_states(cfg, 2, 32, dtype=jnp.float32)
    out = lm.forward(p, toks, cfg, states=s0, write_kv=True, snap_at=n_keep,
                     attend_cache_on_write=True)
    # reference: per-example prefix stepping
    for i, n in enumerate([3, 5]):
        si = lm.init_states(cfg, 1, 32, dtype=jnp.float32)
        oi = lm.forward(p, toks[i:i + 1, :n], cfg, states=si, write_kv=True)
        got = out["states"]["p0"]["tm_s"][0, i]
        ref = oi["states"]["p0"]["tm_s"][0, 0]
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)
        assert int(out["states"]["length"][i]) == n


@pytest.mark.parametrize("which", ["target", "drafter"])
def test_init_honours_param_dtype(which):
    """lm_init / drafter_init create parameters in ``param_dtype``, equal to
    the float32 init cast down (the draw happens in float32 either way)."""
    import dataclasses
    from repro.core.drafter import DrafterConfig, drafter_init
    if which == "target":
        cfg = ModelConfig(num_layers=3, **BASE)
        init = lm.lm_init
    else:
        cfg = DrafterConfig(d_model=64, num_heads=4, num_kv_heads=2,
                            d_ff=128, vocab_size=97, target_feature_dim=192)
        init = drafter_init
    key = jax.random.PRNGKey(7)
    p32 = init(key, cfg)
    p16 = init(key, dataclasses.replace(cfg, param_dtype="bfloat16"))
    assert jax.tree.structure(p32) == jax.tree.structure(p16)
    for a, b in zip(jax.tree.leaves(p32), jax.tree.leaves(p16)):
        assert a.dtype == jnp.float32 and b.dtype == jnp.bfloat16
        np.testing.assert_array_equal(np.asarray(a.astype(jnp.bfloat16)),
                                      np.asarray(b))
