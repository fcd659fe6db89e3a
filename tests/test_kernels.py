"""Per-kernel interpret-mode validation against the ref.py oracles,
sweeping shapes / dtypes / GQA groups / masks (assignment item c)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels import flash_attention as fa

# These kernels TARGET TPU; on this CPU-only container they execute in
# Pallas interpret mode (see pytest.ini for the marker contract).
pytestmark = pytest.mark.pallas


def _rand(key, shape, dtype):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


CASES = [
    # (B, Hq, Hkv, Tq, Tkv, D, causal, window, softcap, dtype)
    (1, 2, 2, 128, 128, 64, True, None, None, jnp.float32),
    (2, 4, 2, 128, 256, 64, True, None, None, jnp.bfloat16),
    (1, 8, 2, 256, 256, 128, True, None, 50.0, jnp.bfloat16),
    (2, 2, 1, 128, 384, 64, True, 100, None, jnp.float32),
    (1, 4, 4, 64, 512, 64, False, None, None, jnp.float32),
    (2, 4, 2, 100, 300, 64, True, None, None, jnp.float32),  # ragged pads
]


@pytest.mark.parametrize("case", CASES)
def test_flash_forward_matches_ref(case):
    b, hq, hkv, tq, tkv, d, causal, window, cap, dtype = case
    ks = jax.random.split(jax.random.PRNGKey(hash(case) % 2 ** 31), 3)
    q = _rand(ks[0], (b, hq, tq, d), dtype)
    k = _rand(ks[1], (b, hkv, tkv, d), dtype)
    v = _rand(ks[2], (b, hkv, tkv, d), dtype)
    q_off = tkv - tq
    kv_len = tkv - 7
    o, lse = fa.flash_attention_fwd(
        q, k, v, causal=causal, q_offset=q_off, window=window, kv_len=kv_len,
        attn_softcap=cap, interpret=True)
    o_ref, lse_ref = ref.flash_attention_ref(
        q, k, v, causal=causal, q_offset=q_off, window=window, kv_len=kv_len,
        attn_softcap=cap)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(o_ref, np.float32),
                               rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(lse_ref),
                               rtol=1e-3, atol=1e-3)


@pytest.mark.parametrize("case", [
    (1, 2, 2, 128, 128, 64, True, None, None, jnp.float32),
    (2, 4, 2, 128, 256, 64, True, None, None, jnp.float32),
    (1, 4, 2, 128, 128, 64, True, None, 30.0, jnp.float32),
    (1, 2, 1, 128, 256, 64, True, 64, None, jnp.float32),
])
def test_flash_backward_matches_autodiff(case):
    b, hq, hkv, tq, tkv, d, causal, window, cap, dtype = case
    ks = jax.random.split(jax.random.PRNGKey(hash(case) % 2 ** 31), 3)
    q = _rand(ks[0], (b, hq, tq, d), dtype)
    k = _rand(ks[1], (b, hkv, tkv, d), dtype)
    v = _rand(ks[2], (b, hkv, tkv, d), dtype)
    q_off = tkv - tq

    def f_kernel(q, k, v):
        o = ops.flash_attention(q, k, v, causal=causal, q_offset=q_off,
                                window=window, attn_softcap=cap,
                                interpret=True, layout="BHTD")
        return (o.astype(jnp.float32) ** 2).sum()

    def f_ref(q, k, v):
        o, _ = ref.flash_attention_ref(q, k, v, causal=causal,
                                       q_offset=q_off, window=window,
                                       attn_softcap=cap)
        return (o.astype(jnp.float32) ** 2).sum()

    g1 = jax.grad(f_kernel, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b_, np.float32),
                                   rtol=2e-3, atol=2e-3)


CASC_CASES = [
    # (B, Hq, Hkv, Tq, S, Tb, D, window, cap, rolling, dtype)
    (1, 2, 2, 16, 512, 16, 64, None, None, False, jnp.float32),
    (2, 4, 2, 76, 1024, 76, 64, None, None, False, jnp.bfloat16),
    (1, 8, 2, 32, 2048, 32, 128, None, 50.0, False, jnp.bfloat16),
    (2, 2, 1, 16, 512, 16, 64, 300, None, True, jnp.float32),
    (1, 4, 4, 8, 768, 8, 64, None, None, False, jnp.float32),
]


@pytest.mark.parametrize("case", CASC_CASES)
def test_cascade_matches_ref(case):
    b, hq, hkv, tq, s, tb, d, window, cap, rolling, dtype = case
    ks = jax.random.split(jax.random.PRNGKey(hash(case) % 2 ** 31), 6)
    q = _rand(ks[0], (b, hq, tq, d), dtype)
    ck = _rand(ks[1], (b, hkv, s, d), dtype)
    cv = _rand(ks[2], (b, hkv, s, d), dtype)
    bk = _rand(ks[3], (b, hkv, tb, d), dtype)
    bv = _rand(ks[4], (b, hkv, tb, d), dtype)
    cache_len = jnp.array([s - 5] + [s - 200] * (b - 1))[:b]
    # comb-ish positions: anchor + increasing depths
    q_abs = cache_len[:, None] + jnp.arange(tq)[None, :] % max(tb, 1)
    tree_mask = jnp.tril(jnp.ones((tq, tb), bool))  # chain-ish mask
    o = casc_call = None
    from repro.kernels.ops import cascade_attention
    o = cascade_attention(q, ck, cv, bk, bv, cache_len=cache_len,
                          q_abs=q_abs, tree_mask=tree_mask, window=window,
                          attn_softcap=cap, rolling=rolling, n_splits=4,
                          bk=256, interpret=True, layout="BHTD")
    o_ref = ref.cascade_attention_ref(
        q, ck, cv, bk, bv, cache_len=cache_len, q_abs=q_abs,
        tree_mask=tree_mask, window=window, attn_softcap=cap,
        rolling=rolling)
    tol = 2e-2 if dtype == jnp.bfloat16 else 3e-5
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(o_ref, np.float32),
                               rtol=tol, atol=tol)


# Ragged + sliding-window sweep at page-aligned and page-straddling cache
# lengths (the boundaries the paged layout makes interesting; bk=64 below
# doubles as the page size so "aligned" means a block/page boundary).
RAGGED_CASES = [
    # (cache_lens, window, rolling)
    ((512, 256), None, False),        # page-aligned, ragged batch
    ((505, 250), None, False),        # page-straddling, ragged batch
    ((512, 256), 96, False),          # aligned + sliding window
    ((505, 131), 96, False),          # straddling + sliding window
    ((505, 250), 200, True),          # straddling + window + rolling buffer
]


@pytest.mark.parametrize("case", RAGGED_CASES)
def test_cascade_ragged_window_boundaries(case):
    """Dense cascade kernel vs oracle on per-example cache lengths that sit
    exactly on / just off KV-block boundaries, with sliding windows."""
    cache_lens, window, rolling = case
    b, hq, hkv, tq, s, d = len(cache_lens), 4, 2, 10, 512, 64
    ks = jax.random.split(jax.random.PRNGKey(42), 5)
    q = _rand(ks[0], (b, hq, tq, d), jnp.float32)
    ck = _rand(ks[1], (b, hkv, s, d), jnp.float32)
    cv = _rand(ks[2], (b, hkv, s, d), jnp.float32)
    bk = _rand(ks[3], (b, hkv, tq, d), jnp.float32)
    bv = _rand(ks[4], (b, hkv, tq, d), jnp.float32)
    cache_len = jnp.asarray(cache_lens)
    q_abs = cache_len[:, None] + jnp.arange(tq)[None, :]
    tree_mask = jnp.tril(jnp.ones((tq, tq), bool))
    o = ops.cascade_attention(q, ck, cv, bk, bv, cache_len=cache_len,
                              q_abs=q_abs, tree_mask=tree_mask,
                              window=window, rolling=rolling, n_splits=4,
                              bk=64, interpret=True, layout="BHTD")
    o_ref = ref.cascade_attention_ref(
        q, ck, cv, bk, bv, cache_len=cache_len, q_abs=q_abs,
        tree_mask=tree_mask, window=window, rolling=rolling)
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(o_ref, np.float32),
                               rtol=3e-5, atol=3e-5)


# Rolling-buffer position recovery at ADVERSARIAL capacities: the modulus
# ``cap`` the kernel recovers absolute positions with must be the TRUE
# buffer capacity, not the split-padded extent — every capacity below is
# non-power-of-two and most are non-bk-aligned (bk=64), which is exactly
# where the old ``cap=s_pad`` plumbing recovered wrong positions.
ROLLING_CASES = [
    # (cap, window, cache_lens)  — lens mix pre-wrap (len <= cap) and
    # full wraparound (len > cap, every slot live and rolled)
    (97, 97, (40, 150)),          # prime cap, pre-wrap + wrapped
    (97, 50, (96, 300)),          # window < cap
    (100, 100, (100, 257)),       # len == cap boundary + deep wrap
    (131, 96, (70, 200)),         # prime, non-bk-aligned window
    (505, 505, (505, 711)),       # > bk, straddles 7.9 blocks
    (509, 200, (300, 1000)),      # prime > bk, deep wrap, small window
    (24, 24, (5, 30)),            # cap < bk (single sub-block)
]


@pytest.mark.parametrize("case", ROLLING_CASES)
def test_cascade_rolling_nonaligned_capacity_matches_ref(case):
    """Dense cascade kernel vs oracle over ROLLING buffers at
    non-block-aligned capacities x window sizes x ragged cache_len
    (including len > cap wraparound) — the tentpole bug regression."""
    cap, window, cache_lens = case
    b, hq, hkv, tq, d = len(cache_lens), 4, 2, 6, 32
    ks = jax.random.split(jax.random.PRNGKey(hash(case) % 2 ** 31), 5)
    q = _rand(ks[0], (b, hq, tq, d), jnp.float32)
    ck = _rand(ks[1], (b, hkv, cap, d), jnp.float32)
    cv = _rand(ks[2], (b, hkv, cap, d), jnp.float32)
    bkv = _rand(ks[3], (b, hkv, tq, d), jnp.float32)
    bvv = _rand(ks[4], (b, hkv, tq, d), jnp.float32)
    cache_len = jnp.asarray(cache_lens)
    q_abs = cache_len[:, None] + jnp.arange(tq)[None, :]
    tree_mask = jnp.tril(jnp.ones((tq, tq), bool))
    o = ops.cascade_attention(q, ck, cv, bkv, bvv, cache_len=cache_len,
                              q_abs=q_abs, tree_mask=tree_mask,
                              window=window, rolling=True, n_splits=4,
                              bk=64, interpret=True, layout="BHTD")
    o_ref = ref.cascade_attention_ref(
        q, ck, cv, bkv, bvv, cache_len=cache_len, q_abs=q_abs,
        tree_mask=tree_mask, window=window, rolling=True)
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(o_ref, np.float32),
                               rtol=3e-5, atol=3e-5)


def test_cascade_phase1_split_count_invariant():
    """cascade_phase1 pads the cache up to the requested split grid
    instead of degrading split-K: effective splits ==
    min(n_splits, ceil(S / bk)) even at prime-ish capacities (the old
    divisibility loop collapsed e.g. S=509, bk=64 to ONE split)."""
    from repro.kernels import cascade_attention as casc
    b, hq, hkv, tq, d = 1, 2, 2, 4, 32
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    for s, n_req, bk, want in [(509, 8, 64, 8),   # prime: used to be 1
                               (505, 4, 64, 4),   # non-aligned
                               (512, 8, 64, 8),   # aligned: unchanged
                               (100, 8, 64, 2),   # short cache clamps
                               (24, 4, 64, 1)]:   # cap < bk
        q = _rand(ks[0], (b, hq, tq, d), jnp.float32)
        ck = _rand(ks[1], (b, hkv, s, d), jnp.float32)
        cv = _rand(ks[2], (b, hkv, s, d), jnp.float32)
        acc, m, l = casc.cascade_phase1(
            q, ck, cv, cache_len=jnp.array([s]),
            q_abs=jnp.arange(tq)[None] + s, n_splits=n_req, bk=bk,
            interpret=True)
        got = acc.shape[2]
        assert got == want == min(n_req, -(-s // min(bk, s))), (
            s, n_req, bk, got, want)
        assert m.shape[2] == l.shape[2] == got


PAGED_CASES = [
    # (B, Hq, Hkv, Tq, page, mp, n_phys, cache_lens, window[, n_splits=4])
    (2, 4, 2, 12, 64, 8, 20, (512, 256), None),     # page-aligned
    (2, 4, 2, 12, 64, 8, 20, (505, 250), None),     # page-straddling
    (2, 4, 2, 12, 64, 8, 20, (505, 131), 100),      # straddling + window
    (1, 8, 2, 16, 128, 4, 7, (333,), None),         # GQA 4, odd pool
    (3, 2, 2, 8, 32, 6, 24, (192, 100, 65), 64),    # 3-way ragged + window
    (2, 4, 2, 8, 64, 7, 15, (410, 230), None),      # PRIME max_pages:
    # the table pads to keep 4-way split-K instead of collapsing to 1
    # GQA 8 at the verify's 76 tree nodes; 57 pages pad to 64 over 8
    # splits, and both rows end before split 2: whole splits are dead
    (2, 16, 2, 76, 64, 57, 120, (700, 1023), None, 8),
    # GQA 4 with one row shorter than a page (its splits past 0 are dead)
    (2, 8, 2, 16, 64, 8, 20, (37, 300), None),
    # ragged 3 rows, each ending part-way into a split
    (3, 8, 4, 12, 32, 12, 40, (100, 230, 5), None),
]


@pytest.mark.parametrize("case", PAGED_CASES)
def test_cascade_paged_matches_ref(case):
    """Paged cascade kernel (scalar-prefetch page-table index_map) vs the
    gather-then-dense oracle, over shuffled disjoint page tables with
    unallocated sentinel tails."""
    b, hq, hkv, tq, page, mp, n_phys, cache_lens, window = case[:9]
    n_splits = case[9] if len(case) > 9 else 4
    d = 64
    rng = np.random.default_rng(hash(case) % 2 ** 31)
    ks = jax.random.split(jax.random.PRNGKey(hash(case) % 2 ** 31), 5)
    q = _rand(ks[0], (b, hq, tq, d), jnp.float32)
    pk = _rand(ks[1], (n_phys, hkv, page, d), jnp.float32)
    pv = _rand(ks[2], (n_phys, hkv, page, d), jnp.float32)
    bk = _rand(ks[3], (b, hkv, tq, d), jnp.float32)
    bv = _rand(ks[4], (b, hkv, tq, d), jnp.float32)
    # disjoint shuffled page tables sized to each row's cache length;
    # unallocated logical pages carry the out-of-range sentinel
    perm = list(rng.permutation(n_phys))
    pt = np.full((b, mp), n_phys, np.int32)
    for i, cl in enumerate(cache_lens):
        need = -(-int(cl) // page)
        pt[i, :need] = [perm.pop() for _ in range(need)]
    cache_len = jnp.asarray(cache_lens)
    q_abs = cache_len[:, None] + jnp.arange(tq)[None, :]
    tree_mask = jnp.tril(jnp.ones((tq, tq), bool))
    o = ops.cascade_attention_paged(
        q, pk, pv, jnp.asarray(pt), bk, bv, cache_len=cache_len,
        q_abs=q_abs, tree_mask=tree_mask, window=window, n_splits=n_splits,
        interpret=True, layout="BHTD")
    o_ref = ref.cascade_attention_paged_ref(
        q, pk, pv, jnp.asarray(pt), bk, bv, cache_len=cache_len,
        q_abs=q_abs, tree_mask=tree_mask, window=window)
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(o_ref, np.float32),
                               rtol=3e-5, atol=3e-5)


def test_cascade_paged_equals_engine_view():
    """Paged kernel on engine-layout pools == the model's decode read path
    (pool_view gather + attend_cache_plus_block) on the same paged state —
    ties the kernel to the storage subsystem that feeds it."""
    from repro.models import kvcache as kvc
    from repro.models.attention import attend_cache_plus_block
    b, hq, hkv, tq, page, mp, d = 2, 4, 2, 8, 32, 4, 64
    n_phys = b * mp
    ks = jax.random.split(jax.random.PRNGKey(5), 5)
    # engine storage layout: [P, page, Hkv, D]
    pk = _rand(ks[0], (n_phys, page, hkv, d), jnp.float32)
    pv = _rand(ks[1], (n_phys, page, hkv, d), jnp.float32)
    q = _rand(ks[2], (b, tq, hq, d), jnp.float32)        # BTHD
    bk = _rand(ks[3], (b, tq, hkv, d), jnp.float32)
    bv = _rand(ks[4], (b, tq, hkv, d), jnp.float32)
    pt = kvc.identity_page_table(b, mp)
    cache_len = jnp.array([mp * page - 5, 70])
    q_abs = cache_len[:, None] + jnp.arange(tq)[None, :]
    tree_mask = jnp.tril(jnp.ones((tq, tq), bool))

    o1 = ops.cascade_attention_paged(
        q, pk, pv, pt, bk, bv, cache_len=cache_len, q_abs=q_abs,
        tree_mask=tree_mask, n_splits=2, interpret=True, layout="BTHD")
    kk = jnp.concatenate([kvc.pool_view(pk, pt), bk], axis=1)
    vv = jnp.concatenate([kvc.pool_view(pv, pt), bv], axis=1)
    o2 = attend_cache_plus_block(
        q, kk, vv, cache_cap=mp * page, cache_len=cache_len, q_abs=q_abs,
        window=None, extra_mask=tree_mask, attn_softcap=None, impl="dense",
        kv_chunk=128, rolling=False)
    np.testing.assert_allclose(np.asarray(o1, np.float32),
                               np.asarray(o2, np.float32),
                               rtol=3e-5, atol=3e-5)


def test_cascade_equals_engine_reference():
    """Cascade kernel == the engine's _attend_cache_plus_block on the same
    inputs (ties the kernel to the system that uses it)."""
    from repro.models.blocks import _attend_cache_plus_block
    b, hq, hkv, tq, s, d = 2, 4, 2, 12, 256, 64
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    q = _rand(ks[0], (b, tq, hq, d), jnp.float32)
    ck = _rand(ks[1], (b, s, hkv, d), jnp.float32)
    cv = _rand(ks[2], (b, s, hkv, d), jnp.float32)
    bk = _rand(ks[3], (b, tq, hkv, d), jnp.float32)
    bv = _rand(ks[4], (b, tq, hkv, d), jnp.float32)
    cache_len = jnp.array([s - 3, s - 100])
    q_abs = cache_len[:, None] + jnp.arange(tq)[None, :]
    tree_mask = jnp.tril(jnp.ones((tq, tq), bool))

    o1 = ops.cascade_attention(q, ck, cv, bk, bv, cache_len=cache_len,
                               q_abs=q_abs, tree_mask=tree_mask,
                               interpret=True, n_splits=2, bk=128)
    kk = jnp.concatenate([ck, bk], axis=1)
    vv = jnp.concatenate([cv, bv], axis=1)
    o2 = _attend_cache_plus_block(
        q, kk, vv, cache_cap=s, cache_len=cache_len, q_abs=q_abs,
        window=None, extra_mask=tree_mask, attn_softcap=None, impl="dense",
        kv_chunk=128, rolling=False)
    np.testing.assert_allclose(np.asarray(o1, np.float32),
                               np.asarray(o2, np.float32),
                               rtol=3e-5, atol=3e-5)


def test_cascade_paged_pos_stride_offset_shard_contract():
    """The position re-parameterization the kv_seq-sharded verify relies
    on (``distributed/spdecode.sharded_paged_cache_attend``): split every
    page's slots across two "shards" (shard i owns slots
    ``[i*page_loc, (i+1)*page_loc)`` of each page), run the paged phase-1
    kernel per shard with ``pos_stride=global page`` /
    ``pos_offset=i*page_loc``, LSE-merge the partials across shards, and
    the result must equal the dense cascade over the unsharded cache."""
    from repro.kernels import cascade_attention as casc
    b, hq, hkv, tq, d = 2, 4, 2, 6, 16
    page, mp, nsh = 8, 4, 2
    page_loc = page // nsh
    s = mp * page
    ks = jax.random.split(jax.random.PRNGKey(9), 5)
    q = _rand(ks[0], (b, hq, tq, d), jnp.float32)
    ck = _rand(ks[1], (b, hkv, s, d), jnp.float32)
    cv = _rand(ks[2], (b, hkv, s, d), jnp.float32)
    bk = _rand(ks[3], (b, hkv, tq, d), jnp.float32)
    bv = _rand(ks[4], (b, hkv, tq, d), jnp.float32)
    # ragged: row 1's live length leaves one shard of its tail page empty
    cache_len = jnp.array([s - 3, 17])
    q_abs = cache_len[:, None] + jnp.arange(tq)[None, :]
    tree_mask = jnp.tril(jnp.ones((tq, tq), bool))
    o_ref = ops.cascade_attention(
        q, ck, cv, bk, bv, cache_len=cache_len, q_abs=q_abs,
        tree_mask=tree_mask, n_splits=2, interpret=True, layout="BHTD")

    pt = (jnp.arange(b)[:, None] * mp
          + jnp.tile(jnp.arange(mp)[None], (b, 1))).astype(jnp.int32)
    parts = []
    for i in range(nsh):
        pool_k = np.zeros((b * mp, hkv, page_loc, d), np.float32)
        pool_v = np.zeros_like(pool_k)
        for bb in range(b):
            for pg in range(mp):
                sl = slice(pg * page + i * page_loc,
                           pg * page + (i + 1) * page_loc)
                pool_k[bb * mp + pg] = np.asarray(ck)[bb, :, sl]
                pool_v[bb * mp + pg] = np.asarray(cv)[bb, :, sl]
        parts.append(casc.cascade_phase1_paged(
            q, jnp.asarray(pool_k), jnp.asarray(pool_v), pt,
            cache_len=cache_len, q_abs=q_abs, n_splits=2,
            pos_stride=page, pos_offset=i * page_loc, interpret=True))
    # cross-shard merge = one more split-axis LSE merge (what the psum
    # merge in spdecode computes), folded into phase 2
    acc = jnp.concatenate([p[0] for p in parts], axis=2)
    m = jnp.concatenate([p[1] for p in parts], axis=2)
    l = jnp.concatenate([p[2] for p in parts], axis=2)
    o = casc._merge_with_tree_block(q, bk, bv, acc, m, l,
                                    tree_mask=tree_mask, attn_softcap=None,
                                    scale=d ** -0.5)
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(o_ref, np.float32),
                               rtol=3e-5, atol=3e-5)


# ---- attn_impl="pallas": end-to-end token parity with the gather path ----

def _parity_bundle(**tkw):
    from conftest import tiny_drafter, tiny_target
    from repro.config.base import SpecConfig
    from repro.core import pipeline as pl
    from repro.core.drafter import drafter_init
    from repro.models import lm
    tcfg = tiny_target(vocab=61, dtype="float32", **tkw)
    dcfg = tiny_drafter(vocab=61, gamma=6, dtype="float32", target_cfg=tcfg)
    tp = lm.lm_init(jax.random.PRNGKey(0), tcfg)
    d1 = drafter_init(jax.random.PRNGKey(1), dcfg)
    d2 = drafter_init(jax.random.PRNGKey(2), dcfg)
    spec = SpecConfig(gamma=6, mode="d2sd")
    return pl.SpecBundle(tcfg, dcfg, dcfg, spec, tp, d1, d2)


@pytest.mark.parametrize("cache_impl", ["paged", "dense"])
def test_attn_impl_token_parity_generate(cache_impl):
    """generate() tokens are identical between attn_impl="gather" and
    "pallas" (interpret mode) — the read path is a pure implementation
    choice, asserted on both paged and dense engines."""
    from repro.core import pipeline as pl
    bundle = _parity_bundle()
    prompts = jax.random.randint(jax.random.PRNGKey(4), (2, 7), 0, 61)
    outs = {}
    for impl in ("gather", "pallas"):
        res = pl.generate(pl.with_attn_impl(bundle, impl), prompts, 10,
                          key=jax.random.PRNGKey(7), cache_impl=cache_impl,
                          page_size=8)
        outs[impl] = np.asarray(res["tokens"]).tolist()
    assert outs["gather"] == outs["pallas"]


def test_attn_impl_token_parity_sliding_window_target():
    """Same parity on a mixed local/global target: paged global layers go
    through the paged kernel, sliding-window local layers through the
    DENSE kernel over their rolling buffers (true-capacity modulus,
    window=24 deliberately non-block-aligned), and the mix must still be
    token-identical end to end."""
    from repro.core import pipeline as pl
    bundle = _parity_bundle(layer_pattern=("local", "global"),
                            sliding_window=24)
    prompts = jax.random.randint(jax.random.PRNGKey(5), (2, 9), 0, 61)
    outs = {}
    for impl in ("gather", "pallas"):
        res = pl.generate(pl.with_attn_impl(bundle, impl), prompts, 10,
                          key=jax.random.PRNGKey(7), cache_impl="paged",
                          page_size=8)
        outs[impl] = np.asarray(res["tokens"]).tolist()
    assert outs["gather"] == outs["pallas"]


def test_attn_impl_token_parity_serving_ragged():
    """ServingEngine parity on mixed prompt lengths / budgets: per-row
    cache_len is genuinely ragged (page-straddling tails), and per-request
    tokens must match between read paths."""
    from repro.core import pipeline as pl
    from repro.serving.engine import ServingEngine
    bundle = _parity_bundle()
    rng = np.random.default_rng(11)
    reqs = [(rng.integers(3, 61, size=p).astype(np.int32), n)
            for p, n in [(11, 5), (5, 3), (8, 6), (6, 4)]]
    outs = {}
    for impl in ("gather", "pallas"):
        eng = ServingEngine(pl.with_attn_impl(bundle, impl), batch_size=2,
                            seed=0, cache_impl="paged", page_size=8)
        for p, n in reqs:
            eng.submit(p, max_new=n)
        eng.run()
        outs[impl] = {r.uid: r.out.tolist() for r in eng.done}
    assert outs["gather"] == outs["pallas"]


def test_default_interpret_by_backend(monkeypatch):
    """Interpret mode on CPU, compiled kernels on TPU, and an error on any
    other backend instead of a silent interpreter fallback."""
    assert ops.default_interpret() is True          # the tests run on CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ops.default_interpret() is False
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="gpu"):
        ops.default_interpret()
