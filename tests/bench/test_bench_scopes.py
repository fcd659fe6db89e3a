"""The program's scopes and spans, and the reduction that reads them
(``bench/xplane.py``, ``bench/cycle_trace.py``): the scopes and kernel
names in the lowered programs, the engine's spans in a CPU profiler trace
of the tiny cell, the scope arithmetic on hand-built events, and the
readings of a small trace recorded on a TPU v5e by
``record_trace_scoped.py``."""
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench_checkout import tiny_checkout
from bench import cycle_trace as ct
from bench import harness, model, spec, trace_reduce, xplane
from bench.peaks import peaks
from repro.core import pipeline as pl
from repro.core import state as st
from repro.kernels import ops

DATA = Path(__file__).resolve().parent / "data"


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    root = tiny_checkout(tmp_path_factory.mktemp("scopes"))
    return root, harness.Bench(spec.load_cell(root, "tiny.closed"), 5)


def _names(text):
    return set(re.findall(r"d2sd\.\w+|cascade_read_\w+|flash_attention_\w+",
                          text))


# ------------------------------------------------- scopes in the programs --
def test_the_decode_cycle_carries_every_phase_scope_and_the_kernel(tiny):
    _, b = tiny
    eng = b.eng
    eng.submit(np.arange(1, 11, dtype=np.int32), 4)
    eng.start_wave(width=eng.batch_size)
    low = pl._cycle_jit.lower(b.bundle, eng.wave.state,
                              jax.random.PRNGKey(0), collect_stats=False,
                              shard_tag=None)
    names = _names(low.as_text(debug_info=True))
    assert set(ct.PHASES) | {ct.KERNEL} <= names
    eng.complete_cycle(eng.dispatch_cycle())


def test_the_install_carries_its_scope(tiny):
    _, b = tiny
    eng = b.eng
    if eng.wave is None:
        eng.submit(np.arange(1, 11, dtype=np.int32), 4)
        eng.start_wave(width=eng.batch_size)
    w = eng.wave
    table = w.pool.row_table([], w.state.max_pages)
    low = st._install_row_donated.lower(
        b.bundle, w.state, jnp.int32(1), jnp.zeros((16,), jnp.int32),
        jax.random.PRNGKey(0), jnp.asarray(table), temperature=0.0,
        ctx_len=0, true_len=jnp.int32(3))
    assert "d2sd.install" in _names(low.as_text(debug_info=True))


def test_the_dense_cascade_and_flash_kernels_are_named():
    q = jnp.ones((1, 2, 8, 16), jnp.float32)
    kv = jnp.ones((1, 2, 32, 16), jnp.float32)
    dense = jax.jit(lambda q, k, v: ops.cascade_attention(
        q, k, v, k[:, :, :8], v[:, :, :8], cache_len=jnp.int32(20),
        q_abs=20 + jnp.arange(8)[None], tree_mask=jnp.ones((1, 8, 8), bool),
        layout="BHTD")).lower(q, kv, kv)
    assert "cascade_read_dense" in _names(dense.as_text(debug_info=True))

    def loss(q, k, v):
        return ops.flash_attention(q, k, v, layout="BHTD").sum()
    grad = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(q, kv, kv)
    assert {"flash_attention_fwd", "flash_attention_bwd_dq",
            "flash_attention_bwd_dkv"} <= _names(
        grad.as_text(debug_info=True))


# --------------------------------------------- engine spans on the CPU ----
def _contains(outer, inner):
    return outer[1] <= inner[1] and inner[1] + inner[2] <= outer[1] + outer[2]


def test_engine_spans_nest_in_the_bench_spans_of_a_cpu_trace(tiny, tmp_path):
    from jax.profiler import ProfileData
    _, b = tiny
    while b.eng.wave is not None:           # drain the earlier tests' wave
        b.eng.step()
    b.start_traffic()
    d = b.driver
    jax.profiler.start_trace(str(tmp_path))
    for _ in range(2):
        d.step()
    jax.profiler.stop_trace()
    path = sorted(tmp_path.rglob("*.xplane.pb"))[-1]
    bench = [(e.name, e.start_ns, e.duration_ns)
             for p in ProfileData.from_file(str(path)).planes
             if p.name.startswith("/host:") for line in p.lines
             for e in line.events if e.name.startswith("bench.")]
    prog = xplane.read_extra(path)["program_spans"]
    names = {n for n, _, _ in prog}
    assert {"engine.dispatch_cycle", "engine.prepare", "engine.enqueue",
            "engine.admit_idle", "engine.complete_cycle", "engine.readback",
            "engine.bank"} <= names
    for p in prog:                          # each inside a bench.* span
        assert any(_contains(s, p) for s in bench), p
    by = {n: [p for p in prog if p[0] == n] for n in names}
    for child, parent in (("engine.enqueue", "engine.dispatch_cycle"),
                          ("engine.readback", "engine.complete_cycle")):
        for c in by[child]:
            assert any(_contains(p, c) for p in by[parent]), c
    trace = {"ops": [], "modules": [], "spans": bench}
    lo_hi = trace_reduce.window(trace)
    trace["program_spans"] = prog + [("engine.early", lo_hi[0] - 1e6, 1)]
    assert trace_reduce.window(trace) == lo_hi
    # the host seconds, readable without a trace; a parent's time holds
    # its children's
    s = b.eng.span_s
    assert s["engine.dispatch_cycle"] >= s["engine.enqueue"] > 0
    assert s["engine.complete_cycle"] >= s["engine.readback"] > 0


# ----------------------------------------------- arithmetic by hand -------
def _op(kernel, start, dur, heads=4, nodes=ct.TREE_NODES):
    return (f"%{kernel}.3 = (f32[2,{heads},8,{nodes},16]{{4,3,2,1,0}}, "
            f"f32[2,{heads},8,{nodes},1]{{4,3,2,1,0}}) custom-call(%p)",
            start, dur)


V = "jit(decode_cycle)/d2sd.verify/while"
HAND = {
    # one cycle program [0, 200): verify's while [0, 100) holds two calls
    # of the paged read; the drafts, select and commit follow
    "ops": [("%while.5 = (s32[]) while(%t)", 0, 100),
            _op(ct.KERNEL, 10, 30), _op(ct.KERNEL, 50, 30),
            ("%fusion.1 = bf16[2] fusion(%a)", 100, 20),
            ("%fusion.2 = bf16[2] fusion(%a)", 120, 5),
            ("%fusion.3 = bf16[2] fusion(%a)", 125, 25),
            _op(ct.KERNEL, 130, 10, heads=2, nodes=64),    # a drafter's read
            ("%fusion.4 = bf16[2] fusion(%a)", 160, 10),
            ("%copy.1 = bf16[2] copy(%a)", 170, 10),
            ("%fusion.5 = bf16[2] fusion(%a)", 230, 20)],
    "scopes": [V, V + "/body/closed_call/pallas_call:",
               V + "/body/closed_call/pallas_call:",
               "jit(decode_cycle)/d2sd.draft1/dot_general:",
               "jit(decode_cycle)/d2sd.select/top_k:",
               "jit(decode_cycle)/d2sd.draft2/dot_general:",
               "jit(decode_cycle)/d2sd.draft2/pallas_call:",
               "jit(decode_cycle)/d2sd.commit/add:", "",
               "jit(_install_impl)/d2sd.install/dot_general:"],
    "modules": [("jit_decode_cycle(1)", 0, 200),
                ("jit__install_impl(2)", 230, 20)],
    "spans": [("bench.dispatch_cycle", -10, 10),
              ("bench.complete_cycle", 0, 215),
              ("bench.admit_idle", 215, 40)],
    # idle [180, 230) and [250, 255): the engine's spans over it
    "program_spans": [("engine.complete_cycle", 0, 212),
                      ("engine.readback", 0, 195),
                      ("engine.bank", 195, 10),
                      ("engine.admit_idle", 215, 40),
                      ("engine.install", 222, 30)],
}


def test_a_scope_counts_a_nested_kernel_once():
    ms = ct.phase_ms(HAND, -10, 255)
    e = 1e-6                                # ns -> ms, one cycle
    assert ms["d2sd.verify"] == pytest.approx(100 * e)   # not 100 + 60
    assert ms["kernel"] == pytest.approx(60 * e)
    assert ms["d2sd.draft1"] == pytest.approx(20 * e)
    assert ms["d2sd.select"] == pytest.approx(5 * e)
    assert ms["d2sd.draft2"] == pytest.approx(25 * e)
    assert ms["draft"] == pytest.approx(50 * e)
    assert ms["d2sd.commit"] == pytest.approx(10 * e)
    assert ms["cycle"] == pytest.approx(200 * e)
    # the copy, and the program's idle [150, 160) and [180, 200)
    assert ms["unattributed"] == pytest.approx(40 * e)
    assert ct.phase_ms({k: v for k, v in HAND.items() if k != "scopes"},
                       -10, 255) is None


def test_the_verify_read_is_told_from_the_drafters_by_its_shape():
    assert ct.verify_read(HAND["ops"], 4, -10, 255) == (60, 2)
    assert ct.verify_read(HAND["ops"], 2, -10, 255) == (0, 0)
    # a sliding-window layer reads its rolling buffer through the dense
    # kernel; another kernel of the same shape is no read of the cache
    ops = HAND["ops"] + [_op(ct.DENSE_KERNEL, 200, 7),
                         _op("flash_attention_fwd", 210, 5)]
    assert ct.verify_read(ops, 4, -10, 255) == (67, 3)


def test_the_roofline_reader_reads_a_window_architecture(tiny):
    """``cascade_read_roofline`` of the tiny (local, global) decoder of
    ``data/archs/tiny_window.py``: per cycle one dense-kernel call (the
    window layer) and one paged call; the least time counts the window
    layer's ``min(ctx, window)`` tokens."""
    from types import SimpleNamespace as NS
    from bench.driver import Cycle
    root, _ = tiny
    arch_mod, _ = spec.arch_modules(
        root, root / "bench" / "configs" / "tiny-window.json")
    arch = arch_mod.Arch(layers=2, d=64, heads=4, kv_heads=2, head_dim=16,
                         ff=128, vocab=512, eps=1e-6, theta=1e4, tied=False,
                         qkv_bias=False, qk_norm=False, dtype="bfloat16",
                         window=8, pattern=("local", "global"))
    assert [arch.attended(n) for n in (5, 8, 100)] == [10, 16, 108]
    ops = [_op(ct.DENSE_KERNEL, 10, 20), _op(ct.KERNEL, 40, 30)]
    trace = {"ops": ops, "modules": [("jit_decode_cycle(1)", 0, 100)],
             "spans": [("bench.complete_cycle", 0, 100)]}
    peak = {"bf16_flops": 1e13, "hbm_bytes_per_s": 1e12}
    run = NS(arch=arch, peak=peak, trace=trace,
             summary=trace_reduce.summarize(trace),
             cycles=[Cycle(0.0, 0.1, rows=4, lens=[5, 100], n_out=[1, 1],
                           pool_use=0.5)])
    tokens = (5 + 5) + (8 + 100)        # the window layer, the global one
    least = max(4 * 4 * 16 * ct.TREE_NODES * tokens / 1e13,
                2 * 2 * 16 * 2 * tokens / 1e12)
    got = spec.reader(root, "cascade_read_roofline.decode")(run)
    assert got == pytest.approx(100 * least / 50e-9)


def test_idle_goes_to_the_innermost_engine_span():
    # idle: [180, 230) and [250, 255)
    assert trace_reduce.idle_gaps(HAND["ops"], -10, 255)[-2:] == [
        (180, 230), (250, 255)]
    by = ct.idle_by_span(HAND, 180, 255)
    # [212, 215) lies in no engine span
    assert by == {"engine.readback": 15, "engine.bank": 10,
                  "engine.complete_cycle": 7, "engine.admit_idle": 7 + 3,
                  "engine.install": 8 + 2}
    idle, inside = ct.idle_covered_ns(HAND, 180, 255)
    assert (idle, inside) == (55, 55)


def test_the_read_work_of_a_qwen_cycle():
    arch = model.Arch.from_file(
        Path(__file__).resolve().parents[2] / "bench" / "configs"
        / "qwen2.5-3b.json")
    flops, nbytes = ct.read_work(arch, [1260] * 16)
    assert flops == pytest.approx(4 * 16 * 128 * 76 * 1260 * 16 * 36)
    assert flops == pytest.approx(453e9, rel=0.01)
    assert nbytes == pytest.approx(745e6, rel=0.01)
    least = ct.read_least_s(arch, [1260] * 16, peaks("TPU v5 lite"))
    assert least == pytest.approx(flops / 197e12)       # compute-bound


def test_the_phase_split_tool_reads_a_window_and_a_trace(tiny):
    """``bench/phase_split.py`` on the tiny cell's untraced window, with
    the hand-built trace above standing in for the chip's."""
    import time
    from bench import phase_split
    root, _ = tiny
    b = harness.Bench(spec.load_cell(root, "tiny.closed"), 6)
    b.driver = phase_split.SpanDriver(b.eng, b.reqs, time.perf_counter())
    b.driver.step()
    b.driver.step()
    rec = b.window(1.0, None)
    d = b.driver
    assert len(d.spans) == len(d.cycles) > rec["c0"]
    assert all("engine.complete_cycle" in s for s in d.spans)
    out = phase_split.split(b, rec, dict(HAND), peaks("TPU v5 lite"),
                            rec["t_open"] + 0.5)
    assert out["cycles_traced"] == 1
    assert out["phase_ms"]["d2sd.verify"] == pytest.approx(100e-6)
    assert out["readings"]["verify_device_ms"] == pytest.approx(100e-6)
    assert out["readings"]["draft_device_ms"] == pytest.approx(50e-6)
    # idle in engine spans: [150, 160) 10, [180, 230) 47, [250, 255) 5
    assert out["readings"]["host_gap_ms"] == pytest.approx(62e-6)
    assert out["idle_in_any_span"] == 1.0
    assert 0 < out["readings"]["cascade_read_roofline"] < 100
    assert out["tokens_per_s"]["untraced"] > 0
    slow = out["slowest_cycle"]
    assert 0 <= slow["index"] < len(d.cycles) - rec["c0"]
    assert slow["host_ms"] >= slow["median_host_ms"]
    assert "engine.complete_cycle" in slow["span_ms"]


def test_the_roofline_reader_by_hand():
    """``cascade_read_roofline``: two calls with the target's 4 heads (one
    cycle of a 2-layer target) take 60 ns; the drafter's call is left
    out; the least time is the compute bound of 2 rows of context 1."""
    from types import SimpleNamespace as NS
    from bench.driver import Cycle
    from bench_checkout import ROOT
    arch = model.Arch(layers=2, d=64, heads=4, kv_heads=2, head_dim=16,
                      ff=128, vocab=512, eps=1e-6, theta=1e4, tied=False,
                      qkv_bias=False, qk_norm=False, dtype="bfloat16")
    peak = {"bf16_flops": 1e13, "hbm_bytes_per_s": 1e12}
    run = NS(arch=arch, peak=peak, trace=HAND,
             summary=trace_reduce.summarize(HAND),
             cycles=[Cycle(0.0, 0.1, rows=4, lens=[5, 7], n_out=[1, 1],
                           pool_use=0.5),
                     Cycle(0.1, 0.2, rows=4, lens=[1, 1], n_out=[1, 1],
                           pool_use=0.5)])
    flops = 2 * 4 * 4 * 16 * ct.TREE_NODES * 2      # layers, 4 Hq D T ctx
    nbytes = 2 * 2 * 2 * 16 * 2 * 2                 # layers, 2 Hkv D 2B ctx
    least = max(flops / 1e13, nbytes / 1e12)
    assert least == flops / 1e13
    got = spec.reader(ROOT, "cascade_read_roofline.decode")(run)
    assert got == pytest.approx(100 * least / 60e-9)
    run.trace = {k: v for k, v in HAND.items() if k != "scopes"}
    assert spec.reader(ROOT, "cascade_read_roofline.decode")(run) == \
        pytest.approx(got)                          # no scopes needed
    run.trace = None
    assert spec.reader(ROOT, "cascade_read_roofline.decode")(run) is None


def test_the_device_clock_is_put_on_the_host_clock_by_the_dispatches():
    # two cycles 100 ns apart; the device shows their runs 3 and 5 ns
    # before their enqueues start, and their ends 7 and 9 ns before their
    # read-backs end
    inf = float("inf")
    tr = {"ops": [("%a = f32[] add()", 7, 2), ("%a = f32[] add()", 105, 2)],
          "modules": [("jit_decode_cycle(1)", 7, 2),
                      ("jit_decode_cycle(1)", 105, 2)],
          "program_spans": [("engine.enqueue", 10, 1),
                            ("engine.readback", 12, 4),
                            ("engine.enqueue", 110, 1),
                            ("engine.readback", 112, 4)]}
    assert ct.device_lag_ns(tr) == (5, 7)
    moved = ct.on_host_clock(tr)
    assert moved["ops"] == [("%a = f32[] add()", 12, 2),
                            ("%a = f32[] add()", 110, 2)]
    assert moved["program_spans"] == tr["program_spans"]
    # a run dispatched before the trace began is no enqueue's nearest
    tr["modules"].insert(0, ("jit_decode_cycle(1)", -95, 2))
    assert ct.device_lag_ns(tr) == (5, 7)
    tr["program_spans"] = []                          # nothing pairs
    assert ct.device_lag_ns(tr) == (0, inf)
    tr["program_spans"] = [("engine.enqueue", 5, 1),     # runs start 2 ns
                           ("engine.enqueue", 103, 1)]   # after them
    assert ct.device_lag_ns(tr) == (0, inf)


def test_the_readings_of_the_scoped_trace_recorded_on_the_chip():
    """The trace ``record_trace_scoped.py`` recorded on one TPU v5e: three
    cycles of a ``decode_cycle`` program with every phase scope, a scan of
    3 layers over ``cascade_read_paged`` in ``d2sd.verify``, and host
    sleeps of 2 ms in ``engine.bank`` and 1 ms in ``engine.admit_idle``."""
    raw = xplane.load_json(DATA / "recorded_trace_scoped.json")
    lo, hi = trace_reduce.window(raw)
    assert trace_reduce.window(raw) == trace_reduce.window(
        {k: v for k, v in raw.items() if k != "program_spans"})
    # that chip's device clock reads over a millisecond behind its host's:
    # the first cycle's operations show before the span that dispatched it
    lag, most = ct.device_lag_ns(raw)
    assert 1e6 < lag < most < 3e6
    assert ct.cycles(raw, lo, hi) == 2
    t = ct.on_host_clock(raw)
    assert ct.cycles(t, lo, hi) == 3
    ms = ct.phase_ms(t, lo, hi)
    assert all(ms[p] > 0 for p in ct.PHASES)
    assert 0 < ms["kernel"] < ms["d2sd.verify"]
    assert ms["draft"] == pytest.approx(
        ms["d2sd.draft1"] + ms["d2sd.select"] + ms["d2sd.draft2"])
    # the phases cover the program but for its copies and loop control
    assert 0 <= ms["unattributed"] < 0.05 * ms["cycle"]
    ns, calls = ct.verify_read(t["ops"], 4, lo, hi)
    assert calls == 3 * 3
    assert ns * 1e-6 == pytest.approx(3 * ms["kernel"])    # by shape, scope
    # each sleep leaves the device idle in its span; all idle time lies
    # in some span
    idle = ct.idle_by_span(t, lo, hi)
    assert max(idle, key=idle.get) == "engine.bank"
    assert idle["engine.bank"] > 3 * 2e6
    assert idle["engine.admit_idle"] > 3 * 1e6
    total, inside = ct.idle_covered_ns(t, lo, hi)
    assert inside > 0.99 * total
    from types import SimpleNamespace as NS
    from bench.driver import Cycle
    from bench_checkout import ROOT
    arch = model.Arch(layers=3, d=512, heads=4, kv_heads=4, head_dim=128,
                      ff=1024, vocab=512, eps=1e-6, theta=1e4, tied=False,
                      qkv_bias=False, qk_norm=False, dtype="bfloat16")
    run = NS(arch=arch, peak=peaks("TPU v5 lite"), trace=t,
             summary=trace_reduce.summarize(t),
             cycles=[Cycle(0.0, 0.1, rows=2, lens=[16, 16], n_out=[1, 1],
                           pool_use=0.5)] * 3)
    share = spec.reader(ROOT, "cascade_read_roofline.decode")(run)
    least = 3 * ct.read_least_s(arch, [16, 16], run.peak)
    assert share == pytest.approx(100 * least / (ns * 1e-9))
    assert 0 < share <= 100
