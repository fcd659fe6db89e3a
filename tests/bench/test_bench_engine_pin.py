"""``PinnedEngine`` overrides three methods of the program's
``ServingEngine`` (``_install_batch``, ``_next_wave``, ``start_wave``)
and calls ``_install`` from them. These
tests fail when the engine under those methods changes its signatures
or its behaviour, so that a change to the engine cannot silently change
what the benchmark times."""
import inspect

import numpy as np
import pytest

from bench_checkout import tiny_checkout
from bench import harness, model, spec, traffic
from bench.driver import PinnedEngine
from repro.serving.engine import Request, ServingEngine


def test_the_overridden_engine_methods_keep_their_signatures():
    def params(f):
        return [(p.name, p.default) for p in
                inspect.signature(f).parameters.values()]
    e = inspect.Parameter.empty
    assert params(ServingEngine._install_batch) == [
        ("self", e), ("grp", e), ("pad", e), ("warm", False)]
    assert params(ServingEngine._next_wave) == [("self", e)]
    assert params(ServingEngine.start_wave) == [("self", e), ("width", None)]
    assert params(ServingEngine._install) == [
        ("self", e), ("slot", e), ("r", e), ("prefix_len", None)]
    # the hooks are still reached from the public calls
    assert "self._next_wave()" in inspect.getsource(ServingEngine.start_wave)
    assert "self._install_batch(" in inspect.getsource(
        ServingEngine._install_group)


@pytest.fixture(scope="module")
def tiny_bundle(tmp_path_factory):
    root = tiny_checkout(tmp_path_factory.mktemp("pin"))
    return harness.Bench(spec.load_cell(root, "tiny.closed"), 3).bundle


def _engine(bundle, largest):
    return PinnedEngine(bundle, batch_size=4, cache_impl="paged",
                        page_size=harness.PAGE, prefix_cache=False,
                        bucket_sizes=(16, 32, 64), pool_pages=16,
                        largest=largest)


def test_every_wave_is_sized_for_the_largest_request(tiny_bundle):
    eng = _engine(tiny_bundle, largest=(40, 24))
    g = model.GAMMA
    eng.submit(np.arange(1, 9, dtype=np.int32), 4)
    assert eng.start_wave(width=4)
    w = eng.wave
    largest = Request(99, np.zeros((40,), np.int32), 24)
    assert w.state.max_pages == eng._pages_needed(largest, g)
    assert w.bufs.shape[1] == 24 + g + 1
    assert eng._fits(largest)
    # the sizing request is neither queued nor installed
    assert eng.queue == []
    assert [r.uid for r in w.requests if r is not None] == [0]
    assert eng.stats["installs"] == 1


@pytest.mark.parametrize("pinned", [True, False], ids=["pinned", "engine"])
def test_every_install_call_holds_one_request(tiny_bundle, pinned):
    """Three same-bucket admissions in one cycle: the engine groups them
    into one call, the pin makes three."""
    eng = (_engine(tiny_bundle, largest=(40, 40)) if pinned else
           ServingEngine(tiny_bundle, batch_size=4, cache_impl="paged",
                         page_size=harness.PAGE, prefix_cache=False,
                         bucket_sizes=(16, 32, 64), pool_pages=16))
    eng.submit(np.arange(1, 9, dtype=np.int32), 30)
    eng.start_wave(width=4)
    n0, calls0 = eng.stats["installs"], eng.stats["install_calls"]
    for i in range(3):
        eng.submit(np.arange(2 + i, 12 + i, dtype=np.int32), 4)
    h = eng.dispatch_cycle()
    assert eng.admit_idle() == 3
    eng.complete_cycle(h)
    assert eng.stats["installs"] - n0 == 3
    assert eng.stats["install_calls"] - calls0 == (3 if pinned else 1)


@pytest.mark.parametrize("out_median,out_max", [(12, 24), (24, 48)])
def test_the_traffic_uses_only_programs_the_warm_up_ran(tmp_path, out_median,
                                                        out_max):
    """Every install the traffic makes, in set-up and in the window, has
    the shape of one the warm-up made: its bucket and the wave geometry.
    With outputs up to 48 the steady-state starts reach a bucket (128)
    whose full length would need a wider page table than the pinned
    one."""
    import json
    import time
    root = tiny_checkout(tmp_path)
    mix_file = root / "bench" / "mixes" / "tiny-mix.json"
    mix = json.loads(mix_file.read_text())
    mix["output"].update(median=out_median, max=out_max)
    mix["buckets"] = [16, 32, 64, 128]
    mix_file.write_text(json.dumps(mix))
    b = harness.Bench(spec.load_cell(root, "tiny.closed"), 11)
    b.warm_up()
    warmed = set(b.eng._install_shapes)
    b.start_traffic()
    b.driver.run_until(time.perf_counter() + 1.0)
    assert b.eng.stats["installs"] > len(warmed) + b.mix["batch"]
    assert b.eng._install_shapes <= warmed
    assert max(s[1] for s in warmed) == harness.bucket(
        traffic.max_start_prompt(b.mix), b.ladder)
