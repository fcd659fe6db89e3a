"""Paged KV-cache subsystem: pool/page-table primitives, token parity of
``cache_impl="paged"`` against dense across the whole stack, page-granular
serving admission, and the copy-free slot-refill contract."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config.base import SpecConfig
from repro.core import pipeline as pl
from repro.core.drafter import drafter_init
from repro.core.state import install_row, prefill_row, refill_copy_bytes
from repro.models import kvcache as kvc
from repro.models import lm
from repro.serving.engine import ServingEngine

from conftest import tiny_target, tiny_drafter, pure_greedy

GAMMA = 5
PAGE = 8


def _bundle(tcfg, gamma=GAMMA):
    dcfg = tiny_drafter(vocab=tcfg.vocab_size, gamma=gamma, dtype="float32",
                        target_cfg=tcfg)
    tp = lm.lm_init(jax.random.PRNGKey(0), tcfg)
    d1 = drafter_init(jax.random.PRNGKey(1), dcfg)
    d2 = drafter_init(jax.random.PRNGKey(2), dcfg)
    spec = SpecConfig(gamma=gamma, top_k_branches=2, mode="d2sd")
    return pl.SpecBundle(tcfg, dcfg, dcfg, spec, tp, d1, d2)


@pytest.fixture(scope="module")
def bundle():
    return _bundle(tiny_target(vocab=61, dtype="float32"))


# ------------------------------------------------------------- primitives --
def test_pool_scatter_view_roundtrip():
    """Random logical writes through the page table land exactly where a
    dense cache would put them (view == simulated dense buffer)."""
    rng = np.random.default_rng(0)
    b, mp, page, h, d = 3, 4, 8, 2, 4
    n_phys = 10
    perm = list(rng.permutation(n_phys))
    pt = np.full((b, mp), n_phys, np.int32)
    alloc = [4, 2, 3]                       # pages per row (ragged)
    for i, n in enumerate(alloc):
        pt[i, :n] = [perm.pop() for _ in range(n)]
    pool = jnp.zeros((n_phys, page, h, d), jnp.float32)
    dense = np.zeros((b, mp * page, h, d), np.float32)

    for start, t in ((0, 11), (11, 5), (16, 9)):
        new = rng.normal(size=(b, t, h, d)).astype(np.float32)
        pos = start + np.arange(t)[None, :] + np.zeros((b, 1), np.int32)
        valid = pos < (np.asarray(alloc) * page)[:, None]
        pool = kvc.pool_scatter(pool, jnp.asarray(pt), jnp.asarray(new),
                                jnp.asarray(pos))
        for i in range(b):
            for j in range(t):
                if valid[i, j]:
                    dense[i, pos[i, j]] = new[i, j]
    view = np.asarray(kvc.pool_view(pool, jnp.asarray(pt)))
    for i, n in enumerate(alloc):
        np.testing.assert_array_equal(view[i, : n * page],
                                      dense[i, : n * page])


def test_pool_scatter_stacked_layers():
    """[L, P, page, H, D] pools (feature caches / scanned periods) scatter
    per layer with one shared table."""
    l, b, mp, page, h, d = 2, 2, 2, 4, 1, 3
    pool = jnp.zeros((l, b * mp, page, h, d), jnp.float32)
    pt = kvc.identity_page_table(b, mp)
    new = jnp.arange(l * b * 3 * h * d, dtype=jnp.float32).reshape(
        l, b, 3, h, d)
    pos = jnp.asarray([[2, 3, 4], [0, 1, 2]])
    pool = kvc.pool_scatter(pool, pt, new, pos)
    view = np.asarray(kvc.pool_view(pool, pt))       # [L, B, mp*page, H, D]
    np.testing.assert_array_equal(view[:, 0, 2:5], np.asarray(new)[:, 0])
    np.testing.assert_array_equal(view[:, 1, 0:3], np.asarray(new)[:, 1])
    assert (view[:, 0, :2] == 0).all() and (view[:, 1, 3:] == 0).all()


def test_page_pool_alloc_free_invariants():
    pool = kvc.PagePool(6, PAGE)
    a = pool.alloc(4)
    assert len(set(a)) == 4 and pool.free_pages == 2
    assert pool.alloc(3) is None            # no partial grants
    b = pool.alloc(2)
    assert pool.free_pages == 0 and pool.peak_in_use == 6
    pool.free(a)
    assert pool.free_pages == 4 and pool.pages_in_use == 2
    c = pool.alloc(4)
    assert set(c) == set(a)                 # pages are recycled
    with pytest.raises(AssertionError):
        pool.free([c[0], c[0]])             # double free is a bug
    t = pool.row_table(b, max_pages=5)
    assert list(t[:2]) == b and (t[2:] == kvc.PAGE_SENTINEL).all()


# ----------------------------------------------------------- token parity --
def test_generate_paged_token_identity(bundle):
    """generate() with paged KV == dense == pure greedy, page-straddling
    prompt lengths included."""
    v = bundle.target_cfg.vocab_size
    prompts = jax.random.randint(jax.random.PRNGKey(3), (3, 9), 0, v)
    kd = jax.random.PRNGKey(7)
    dense = pl.generate(bundle, prompts, max_new=12, key=kd,
                        collect_stats=False)
    paged = pl.generate(bundle, prompts, max_new=12, key=kd,
                        collect_stats=False, cache_impl="paged",
                        page_size=PAGE)
    assert np.array_equal(dense["tokens"], paged["tokens"])
    ref = np.asarray(pure_greedy(bundle.target_params, bundle.target_cfg,
                                 prompts, 12))
    assert np.array_equal(np.asarray(paged["tokens"]), ref)
    assert dense["n_cycles"] == paged["n_cycles"]


def test_generate_ondevice_paged_token_identity(bundle):
    """The fully fused while_loop path works over paged states too."""
    v = bundle.target_cfg.vocab_size
    prompts = jax.random.randint(jax.random.PRNGKey(4), (2, 7), 0, v)
    kd = jax.random.PRNGKey(9)
    host = pl.generate(bundle, prompts, max_new=10, key=kd,
                       collect_stats=False, cache_impl="paged",
                       page_size=PAGE)
    dev = pl.generate_ondevice(bundle, prompts, max_new=10, key=kd,
                               cache_impl="paged", page_size=PAGE)
    assert np.array_equal(host["tokens"], np.asarray(dev["tokens"]))
    assert host["n_cycles"] == dev["n_cycles"]


def test_paged_local_global_hybrid_parity():
    """Sliding-window (local) layers keep dense rolling buffers while
    global layers page — the mix must stay token-exact."""
    tcfg = tiny_target(vocab=53, dtype="float32",
                       layer_pattern=("local", "global"), sliding_window=16)
    b = _bundle(tcfg, gamma=4)
    prompts = jax.random.randint(jax.random.PRNGKey(3), (2, 8), 0, 53)
    kd = jax.random.PRNGKey(9)
    dense = pl.generate(b, prompts, max_new=10, key=kd, collect_stats=False)
    paged = pl.generate(b, prompts, max_new=10, key=kd, collect_stats=False,
                        cache_impl="paged", page_size=PAGE)
    assert np.array_equal(dense["tokens"], paged["tokens"])
    ref = np.asarray(pure_greedy(b.target_params, tcfg, prompts, 10))
    assert np.array_equal(np.asarray(paged["tokens"]), ref)


def test_paged_hybrid_recurrent_global_parity():
    """Hybrid recurrent+global target: the state-replay verifier's branch
    fold must replicate page-table rows but NOT the (batch-free) pools,
    and the snap_at replay writes page-wise."""
    tcfg = tiny_target(vocab=47, dtype="float32",
                       layer_pattern=("recurrent", "global"))
    b = _bundle(tcfg, gamma=4)
    from repro.core.verify import select_backend
    assert select_backend(tcfg).name == "state_replay"
    prompts = jax.random.randint(jax.random.PRNGKey(3), (2, 7), 0, 47)
    kd = jax.random.PRNGKey(5)
    dense = pl.generate(b, prompts, max_new=8, key=kd, collect_stats=False)
    paged = pl.generate(b, prompts, max_new=8, key=kd, collect_stats=False,
                        cache_impl="paged", page_size=PAGE)
    assert np.array_equal(dense["tokens"], paged["tokens"])
    ref = np.asarray(pure_greedy(b.target_params, tcfg, prompts, 8))
    assert np.array_equal(np.asarray(paged["tokens"]), ref)


def test_paged_state_replay_backend_parity():
    """Attention-free target (rwkv): the state-replay verifier runs with
    paged feature caches (the only paged leaves) — parity must hold."""
    tcfg = tiny_target(vocab=43, dtype="float32", layer_pattern=("rwkv",),
                       rwkv_head_dim=16)
    b = _bundle(tcfg, gamma=4)
    prompts = jax.random.randint(jax.random.PRNGKey(5), (2, 6), 0, 43)
    kd = jax.random.PRNGKey(11)
    dense = pl.generate(b, prompts, max_new=8, key=kd, collect_stats=False)
    paged = pl.generate(b, prompts, max_new=8, key=kd, collect_stats=False,
                        cache_impl="paged", page_size=4)
    assert np.array_equal(dense["tokens"], paged["tokens"])


# ------------------------------------------------------ install / refill ---
def test_paged_prefill_row_isolated(bundle):
    """Paged slot install: adopted row prefills into its own pages; every
    other row's logical view, length, and anchor are bit-identical."""
    v = bundle.target_cfg.vocab_size
    prompts = jax.random.randint(jax.random.PRNGKey(3), (3, 8), 0, v)
    state = pl.engine_init(bundle, 3, 64, cache_impl="paged", page_size=PAGE)
    state = pl.prefill(bundle, state, prompts)
    newp = jax.random.randint(jax.random.PRNGKey(8), (12,), 0, v)
    st2 = prefill_row(bundle, state, 1, newp, key=jax.random.PRNGKey(11))
    assert int(st2.length[1]) == 12
    assert [int(st2.length[i]) for i in (0, 2)] == \
        [int(state.length[i]) for i in (0, 2)]
    # neighbors' logical feature-cache views untouched
    old = np.asarray(kvc.pool_view(state.d1_feat["k"], state.d1_feat["pt"]))
    new = np.asarray(kvc.pool_view(st2.d1_feat["k"], st2.d1_feat["pt"]))
    np.testing.assert_array_equal(new[:, 0], old[:, 0])
    np.testing.assert_array_equal(new[:, 2], old[:, 2])
    assert not np.array_equal(new[:, 1], old[:, 1])
    # the adopted row's anchor equals a standalone prefill's first token
    ref = np.asarray(pure_greedy(bundle.target_params, bundle.target_cfg,
                                 jnp.asarray(newp)[None], 1))[0]
    assert int(st2.anchor[1]) == int(ref[0])


def test_install_row_donated_matches_prefill_row(bundle):
    """The serving fast path (donated jit install) and the non-donating
    prefill_row agree on the resulting state: integer leaves (tokens,
    lengths, page tables) exactly, float caches to jit-vs-eager rounding."""
    v = bundle.target_cfg.vocab_size
    prompts = jax.random.randint(jax.random.PRNGKey(3), (2, 8), 0, v)
    newp = jax.random.randint(jax.random.PRNGKey(8), (10,), 0, v)
    mk = lambda: pl.prefill(bundle, pl.engine_init(       # noqa: E731
        bundle, 2, 48, cache_impl="paged", page_size=PAGE), prompts)
    mp = mk().max_pages
    a = prefill_row(bundle, mk(), 1, newp, key=jax.random.PRNGKey(2))
    b = install_row(bundle, mk(), 1, newp, key=jax.random.PRNGKey(2),
                    row_table=mp + jnp.arange(mp, dtype=jnp.int32))
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        la, lb = np.asarray(la), np.asarray(lb)
        if np.issubdtype(la.dtype, np.integer) or la.dtype == bool:
            np.testing.assert_array_equal(la, lb)
        else:
            np.testing.assert_allclose(la, lb, rtol=1e-5, atol=1e-6)


def test_refill_copy_bytes_page_order(bundle):
    """The install accounting model: paged installs cost page-order bytes,
    dense installs cost a full max_len row."""
    prompts = jax.random.randint(jax.random.PRNGKey(3), (2, 8), 0, 61)
    dense = pl.prefill(bundle, pl.engine_init(bundle, 2, 256), prompts)
    paged = pl.prefill(bundle, pl.engine_init(
        bundle, 2, 256, cache_impl="paged", page_size=PAGE), prompts)
    bd = refill_copy_bytes(dense, 8)
    bp = refill_copy_bytes(paged, 8)
    assert bp * 8 < bd        # page-order, not max_len-order
    # dense scales with capacity, paged with the prompt
    dense_big = pl.engine_init(bundle, 2, 512)
    assert refill_copy_bytes(dense_big, 8) > 1.8 * bd
    paged_big = pl.engine_init(bundle, 2, 512, cache_impl="paged",
                               page_size=PAGE)
    assert refill_copy_bytes(paged_big, 8) == pytest.approx(bp, rel=0.05)


def test_decode_cycle_paged_inactive_row_frozen(bundle):
    """A masked row of a paged wave freezes its page table AND its pages'
    contents through a decode cycle."""
    v = bundle.target_cfg.vocab_size
    prompts = jax.random.randint(jax.random.PRNGKey(3), (2, 8), 0, v)
    state = pl.engine_init(bundle, 2, 64, cache_impl="paged", page_size=PAGE)
    state = pl.prefill(bundle, state, prompts)
    state = state.replace(active=jnp.asarray([True, False]))
    state2, out = pl.decode_cycle(bundle, state, jax.random.PRNGKey(1),
                                  collect_stats=False)
    n_out = np.asarray(out["n_out"])
    assert n_out[0] >= 1 and n_out[1] == 0
    assert int(state2.length[1]) == int(state.length[1])
    assert int(state2.length[0]) > int(state.length[0])
    # page table frozen for both rows (allocation is install-time only)...
    np.testing.assert_array_equal(np.asarray(state2.d1_feat["pt"]),
                                  np.asarray(state.d1_feat["pt"]))
    # ...and the inactive row's logical view is bit-identical
    old = np.asarray(kvc.pool_view(state.d1_feat["k"], state.d1_feat["pt"]))
    new = np.asarray(kvc.pool_view(state2.d1_feat["k"],
                                   state2.d1_feat["pt"]))
    np.testing.assert_array_equal(new[:, 1], old[:, 1])
    assert not np.array_equal(new[:, 0], old[:, 0])


# ---------------------------------------------------------------- serving --
def _traffic(v, seed=0):
    rng = np.random.default_rng(seed)
    plens = (8, 11, 8, 9, 10)
    wants = (6, 14, 9, 5, 11)
    return [rng.integers(0, v, size=p).astype(np.int32) for p in plens], wants


def _serve(bundle, prompts, wants, **kw):
    eng = ServingEngine(bundle, batch_size=2, **kw)
    for p, n in zip(prompts, wants):
        eng.submit(p, max_new=n)
    stats = eng.run()
    return eng, stats


def test_serving_paged_token_parity_and_page_accounting(bundle):
    """Same traffic through dense and paged engines: identical per-request
    tokens; paged refills allocate/free pages and report page-order
    refill-copy bytes (the PR acceptance criterion)."""
    prompts, wants = _traffic(bundle.target_cfg.vocab_size)
    ed, sd = _serve(bundle, prompts, wants, cache_impl="dense")
    ep, sp = _serve(bundle, prompts, wants, cache_impl="paged",
                    page_size=PAGE)
    outs = lambda e: {r.uid: r.out.tolist() for r in e.done}  # noqa: E731
    assert outs(ed) == outs(ep)
    assert sp["refills"] == sd["refills"] and sp["refills"] > 0
    assert sp["pool_pages"] > 0
    assert 0 < sp["pool_peak_pages"] <= sp["pool_pages"]
    assert 0.0 < sp["pool_utilization"] <= 1.0
    # copy-free refill: paged installs write page-order bytes, a small
    # fraction of the dense row splice
    assert sp["installs"] == sd["installs"]
    assert sp["refill_copy_bytes"] * 3 < sd["refill_copy_bytes"]
    # every request checks out against standalone greedy decoding
    for r in ep.done:
        ref = np.asarray(pure_greedy(
            bundle.target_params, bundle.target_cfg,
            jnp.asarray(prompts[r.uid])[None], r.max_new))[0]
        assert np.array_equal(r.out, ref), r.uid


def test_serving_paged_requires_early_exit(bundle):
    """Legacy all-rows-run mode would let retired slots write through
    stale page tables into freed pages — the engine must refuse it."""
    with pytest.raises(ValueError, match="early_exit"):
        ServingEngine(bundle, cache_impl="paged", early_exit=False)


def test_serving_paged_prefill_burst_pool_pressure(bundle):
    """Regression: max_new<=1 bursts retire during start_wave and
    chain-refill from beyond the pool-sizing candidate window; the initial
    installs must still get their guaranteed pages (install-all before
    retire-any), and every request must complete correctly."""
    v = bundle.target_cfg.vocab_size
    rng = np.random.default_rng(7)
    mk = lambda n: rng.integers(0, v, size=n).astype(np.int32)  # noqa: E731
    # slot 0's burst drains several queue entries (incl. a page-hungry one)
    # before slot 1's big initial request is installed
    reqs = [(mk(6), 1), (mk(10), 12), (mk(6), 1), (mk(6), 1), (mk(12), 10),
            (mk(6), 4)]
    eng = ServingEngine(bundle, batch_size=2, cache_impl="paged",
                        page_size=PAGE)
    for p, n in reqs:
        eng.submit(p, max_new=n)
    stats = eng.run()
    assert len(eng.done) == len(reqs)
    for r in eng.done:
        ref = np.asarray(pure_greedy(
            bundle.target_params, bundle.target_cfg,
            jnp.asarray(reqs[r.uid][0])[None], r.max_new))[0]
        assert np.array_equal(r.out, ref), r.uid
    assert stats["pool_peak_pages"] <= stats["pool_pages"]


def test_serving_paged_pool_reuse_across_retires(bundle):
    """Sustained traffic through a small batch recycles freed pages: the
    pool peak stays at the worst-case concurrent set, not the total
    traffic volume."""
    v = bundle.target_cfg.vocab_size
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, v, size=8).astype(np.int32)
               for _ in range(6)]
    wants = [4] * 6
    ep, sp = _serve(bundle, prompts, wants, cache_impl="paged",
                    page_size=PAGE)
    assert len(ep.done) == 6 and sp["waves"] == 1
    need = -(-(8 + 4 + 2 * GAMMA + 8) // PAGE)        # pages per request
    assert sp["pool_peak_pages"] <= 2 * need          # batch_size concurrent
    for r in ep.done:
        ref = np.asarray(pure_greedy(
            bundle.target_params, bundle.target_cfg,
            jnp.asarray(prompts[r.uid])[None], r.max_new))[0]
        assert np.array_equal(r.out, ref), r.uid


def test_serving_paged_read_page_counters(bundle):
    """``read_live_pages`` / ``read_table_pages`` count, per dispatched
    cycle, the live pages of each active row's committed cache (read off
    the device state) and the table width the paged read walks."""
    from repro.kernels.cascade_attention import paged_table_width
    prompts, wants = _traffic(bundle.target_cfg.vocab_size)
    eng = ServingEngine(bundle, batch_size=2, cache_impl="paged",
                        page_size=PAGE)
    for p, n in zip(prompts, wants):
        eng.submit(p, max_new=n)
    eng.start_wave(width=2)
    live = table = cycles = 0
    while eng.wave is not None and cycles < 6:
        w = eng.wave
        eng._flush_anchors()
        active = eng._host_active()
        lens = np.asarray(w.state.length)[active]
        live += int(sum(-(-int(n) // PAGE) for n in lens))
        table += int(active.sum()) * paged_table_width(w.state.max_pages)
        handle = eng.dispatch_cycle()
        eng.admit_idle()
        eng.complete_cycle(handle)
        cycles += 1
        assert eng.stats["read_live_pages"] == live
        assert eng.stats["read_table_pages"] == table
    assert cycles == 6 and 0 < live < table
