"""The trace reduction (``bench/trace_reduce.py``) on hand-built events
with values worked out by hand, and on a small trace recorded on a TPU
v5e by ``record_trace.py``."""
from pathlib import Path

import pytest

from bench import trace_reduce as tr

DATA = Path(__file__).resolve().parent / "data"

# device ops (ns): [0,10) [5,20) [30,40) [45,50); window [0, 60) from spans
TRACE = {
    "ops": [("fusion.1", 0, 10), ("_phase1_paged_kernel", 5, 15),
            ("fusion.1", 30, 10), ("copy.2", 45, 5)],
    "modules": [("jit_decode_cycle(7)", 0, 20), ("jit_install_rows(3)", 30,
                                                 20)],
    "spans": [("bench.dispatch_cycle", 0, 2), ("bench.complete_cycle", 2,
                                               26),
              ("bench.wait", 28, 4), ("bench.admit_idle", 40, 20)],
}


def test_busy_union_and_idle_share():
    s = tr.summarize(TRACE)
    assert (s["lo"], s["hi"]) == (0, 60)
    # union: [0,20) + [30,40) + [45,50) = 35 ns of 60
    assert s["busy_s"] == pytest.approx(35e-9)
    assert s["window_s"] == pytest.approx(60e-9)
    assert s["idle_share"] == pytest.approx(25 / 60)


def test_gaps_are_labelled_by_the_host_span_over_them():
    gaps = tr.idle_gaps(TRACE["ops"], 0, 60)
    assert gaps == [(20, 30), (40, 45), (50, 60)]
    # [20,30): complete_cycle covers 8 ns, wait 2 ns
    assert tr.label(gaps[0], TRACE["spans"]) == "bench.complete_cycle"
    assert tr.label(gaps[1], TRACE["spans"]) == "bench.admit_idle"
    s = tr.summarize(TRACE)
    assert s["idle_gaps"][0] == ["bench.complete_cycle", pytest.approx(1e-8)]
    assert s["idle_by_span"] == {
        "bench.complete_cycle": pytest.approx(1e-8),
        "bench.admit_idle": pytest.approx(1.5e-8)}


def test_program_and_kernel_time():
    assert tr.by_name(TRACE["ops"]) == {"fusion.1": 20,
                                        "_phase1_paged_kernel": 15,
                                        "copy.2": 5}
    assert tr.matching(TRACE["modules"], ("decode_cycle",)) == (20, 1)
    assert tr.matching(TRACE["ops"], ("paged_kernel",), 0, 30) == (15, 1)
    s = tr.summarize(TRACE, top=2)
    assert s["device_ops"] == [["fusion.1", pytest.approx(2e-8)],
                               ["_phase1_paged_kernel",
                                pytest.approx(1.5e-8)]]


def test_a_trace_without_spans_has_no_window():
    with pytest.raises(ValueError):
        tr.window({"ops": TRACE["ops"], "modules": [], "spans": []})


def _run():
    """A run record with one traced cycle of two active rows."""
    from types import SimpleNamespace as NS
    from bench.driver import Cycle
    from bench.model import Arch
    arch = Arch(layers=2, d=64, heads=4, kv_heads=2, head_dim=16, ff=128,
                vocab=512, eps=1e-6, theta=1e4, tied=False, qkv_bias=False,
                qk_norm=False, dtype="bfloat16")
    cyc = Cycle(0.0, 0.1, rows=4, lens=[100, 300], n_out=[1, 2],
                pool_use=0.25)
    trace = dict(TRACE)
    return NS(arch=arch, peak={"bf16_flops": 1e12, "hbm_bytes_per_s": 1e9},
              cycles=[cyc], trace=trace, summary=tr.summarize(trace),
              window_s=0.5)


def test_readers_arithmetic_by_hand():
    from bench import spec
    from bench_checkout import ROOT
    run = _run()
    read = lambda m: spec.reader(ROOT, m)(run)  # noqa: E731
    assert read("batch_occupancy") == pytest.approx(50.0)
    assert read("pool_use_share") == pytest.approx(25.0)
    # idle share of the hand-built trace: 25 of 60 ns
    assert read("idle_share.decode") == pytest.approx(100 * 25 / 60)
    # one decode_cycle module of 20 ns
    assert read("cycle_device_ms.decode") == pytest.approx(20e-6)
    # mfu: per token 2 x matmul params + 4 x layers x heads x hd x ctx
    p = 2 * (64 * (64 + 2 * 32) + 64 * 64 + 3 * 64 * 128) + 64 * 512
    flops = sum(n * (2 * p + 4 * 2 * 4 * 16 * ln)
                for n, ln in ((1, 100), (2, 300)))
    assert read("mfu") == pytest.approx(100 * flops / 0.5 / 1e12)


def test_recorded_trace():
    """The trace ``record_trace.py`` recorded on one TPU v5e (read from its
    ``.xplane.pb`` by ``load_xplane``, kept as compact JSON): three rounds
    of a 2048² bf16 matrix product and a Pallas kernel under bench spans,
    with a 2 ms host sleep in each round."""
    js = tr.load_json(DATA / "recorded_trace.json")
    assert tr.matching(js["modules"], ("jit_matmul",))[1] == 3
    assert tr.matching(js["modules"], ("jit_double",))[1] == 3
    assert tr.matching(js["ops"], ("tpu_custom_call",))[1] == 3
    s = tr.summarize(js)
    assert len(js["spans"]) == 12
    assert 0 < s["busy_s"] < s["window_s"] < 0.05
    # the sleeps and the host round trips leave the device idle most of
    # the window; every gap lies under some span
    assert s["idle_share"] > 0.5
    assert "bench.wait" in s["idle_by_span"]
