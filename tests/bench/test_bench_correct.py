"""The comparison that decides ``correct``, at tiny widths on the CPU:
the served tokens of the driver loop agree with the plain reference; the
reference's control in a lower precision does not; a run whose timed
path is broken underneath comes out not correct; and an architecture
added as files only is held to its own reference's mathematics."""
import dataclasses
import time

import jax
import pytest

from bench_checkout import tiny_checkout
from bench import harness, spec


@pytest.fixture
def quiet_cache():
    """The harness turns JAX's persistent cache on for this process; put
    it back off so that later tests in the worker are not affected."""
    yield
    from jax.experimental.compilation_cache import compilation_cache as cc
    jax.config.update("jax_compilation_cache_dir", None)
    cc.reset_cache()


def test_served_tokens_agree_with_the_reference_and_the_control_does_not(
        tmp_path, quiet_cache):
    root = tiny_checkout(tmp_path)
    harness.enable_compile_cache(root)
    cell = spec.load_cell(root, "tiny.closed")
    limit = harness.Bench(cell, 1).limits["widest_gap"]
    for seed in (1, 2, 5):
        b = harness.Bench(cell, seed)
        b.warm_up()
        b.start_traffic()
        b.driver.run_until(time.perf_counter() + 3.0)
        b.release_engine()
        got = b.check(control=True)
        assert got["finite"] and got["tokens_checked"] >= 12
        assert got["widest_gap"] <= limit < got["control_widest_gap"], (
            seed, got)


def test_the_check_covers_every_running_row_at_the_window_close(
        tmp_path, quiet_cache):
    """The requests checked are every row running when the window closes
    (up to its last committed token) and every finished request."""
    root = tiny_checkout(tmp_path)
    harness.enable_compile_cache(root)
    b = harness.Bench(spec.load_cell(root, "tiny.closed"), 7)
    b.warm_up()
    b.start_traffic()
    b.driver.run_until(time.perf_counter() + 1.0)
    w, d = b.eng.wave, b.driver
    live = {r.uid: w.bufs[i, : d.by_uid[r.uid].n].tolist()
            for i, r in enumerate(w.requests)
            if r is not None and i not in w.pending_anchor}
    finished = [t for t in d.tracks if t.done]
    assert live and len(live) >= b.mix["batch"] - 1
    b.release_engine()
    kept = {tuple(p.tolist()): s.tolist() for p, s in b.checked}
    assert len(kept) == len(live) + len(finished)
    for uid, toks in live.items():
        assert kept[tuple(d.by_uid[uid].req.prompt.tolist())] == toks
    for t in finished:
        assert kept[tuple(t.req.prompt.tolist())] == \
            t.out[: t.req.max_new].tolist()


def _alter_tokens(eng):
    """A token altered where it is produced: every cycle's first output."""
    cycle, v = eng._cycle, eng.bundle.target_cfg.vocab_size

    def broken(state, key):
        state, out = cycle(state, key)
        out = dict(out, tokens=out["tokens"].at[:, 0].set(
            (out["tokens"][:, 0] + 1) % v))
        return state, out
    eng._cycle = broken


def _state_unchanged(eng):
    """A step that returns its state unchanged (tokens still banked)."""
    cycle = eng._cycle

    def broken(state, key):
        _, out = cycle(state, key)
        return state, out
    eng._cycle = broken


@pytest.mark.parametrize("fault", [None, _alter_tokens, _state_unchanged],
                         ids=["sound", "token_altered", "state_unchanged"])
def test_a_broken_timed_path_is_not_correct(tmp_path, capsys, fault,
                                            quiet_cache):
    root = tiny_checkout(tmp_path)
    res = harness.main(["--workload", "tiny.closed", "--seed", "5",
                        "--seconds", "2", "--trace", "0"],
                       time.perf_counter(), root, require_tpu=False,
                       fault=fault)
    assert res["correct"] is (fault is None), res["checks"]
    out, err = capsys.readouterr()
    assert out.strip().splitlines()[-1].startswith('{"correct"')
    assert err.strip().splitlines()[-1].startswith("check tokens_checked")
    assert res["metrics"]["tokens_per_s"]["value"] > 0
    assert list(res)[-1] == "checks"


def test_a_traced_run_reports_its_per_layer_metrics(tmp_path, capsys,
                                                    monkeypatch, quiet_cache):
    """``--trace 1`` on the CPU, with the device trace (which only a TPU
    writes) replaced by the hand-built one of ``test_bench_trace.py``."""
    from test_bench_trace import TRACE
    monkeypatch.setattr(harness, "read_trace", lambda d: dict(TRACE))
    root = tiny_checkout(tmp_path)
    res = harness.main(["--workload", "tiny.closed", "--seed", "3",
                        "--seconds", "2", "--trace", "1"],
                       time.perf_counter(), root, require_tpu=False)
    assert res["correct"] is True
    assert res["device"]["busy_s"] == pytest.approx(35e-9)
    assert res["device"]["window_s"] == pytest.approx(60e-9)
    assert set(res["metrics"]) == {
        "batch_occupancy", "cycle_device_ms.decode", "pool_use_share",
        "mfu", "idle_share.decode"}
    assert len(res["breakdown"]["device_ops"]) <= 10
    assert res["breakdown"]["idle_gaps"][0][0] == "bench.complete_cycle"


def _window_blind(arch_ref):
    """``arch_ref.tail_logits`` with the sliding windows wider than any
    sequence: every layer attends to the whole context."""
    def tail_logits(w, tokens, start, *, arch, n_out, precision="fp32"):
        return arch_ref.tail_logits(
            w, tokens, start, n_out=n_out, precision=precision,
            arch=dataclasses.replace(arch, window=1 << 30))
    return tail_logits


def test_an_architecture_added_as_files_is_served_and_held_to_its_reference(
        tmp_path, capsys, monkeypatch, quiet_cache):
    """The sliding-window decoder of ``data/archs/tiny_window*.py``, added
    to a checkout by new files and ``BENCHMARK.json`` entries only, is
    served through the engine and agrees with its own reference; the same
    run checked against that reference blind to the window is not
    correct."""
    root = tiny_checkout(tmp_path)
    runs = []

    class Kept(harness.Bench):
        def __init__(self, *args):
            super().__init__(*args)
            runs.append(self)
    monkeypatch.setattr(harness, "Bench", Kept)
    res = harness.main(["--workload", "tiny-window.closed", "--seed", "4",
                        "--seconds", "2", "--trace", "0"],
                       time.perf_counter(), root, require_tpu=False)
    assert res["correct"] is True, res["checks"]
    assert res["metrics"]["tokens_per_s"]["value"] > 0
    b, = runs
    assert b.arch.pattern == ("local", "global") and b.arch.window == 8
    b.tail_logits = _window_blind(spec.load_cell(
        root, "tiny-window.closed").reference)
    correct, checks = harness.verdict(b, b.check())
    assert correct is False
    assert checks["tokens_checked"] == res["checks"]["tokens_checked"]
    assert checks["widest_gap"]["value"] > 10 * checks["widest_gap"]["limit"]
