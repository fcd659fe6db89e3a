"""The traffic generator: deterministic in the seed, within its clips, the
same work for every seed, and a first batch at the steady state."""
import json
from collections import Counter

import numpy as np
import pytest

from bench_checkout import ROOT
from bench import traffic

MIXES = sorted((ROOT / "bench" / "mixes").glob("*.json"))


def _mix(path):
    return json.loads(path.read_text())


@pytest.mark.parametrize("path", MIXES, ids=[p.stem for p in MIXES])
def test_mix_is_deterministic_in_seed(path):
    mix = _mix(path)
    a = traffic.generate(mix, 2**31 + 11, 151936, 40)
    b = traffic.generate(mix, 2**31 + 11, 151936, 40)
    c = traffic.generate(mix, 2**31 + 12, 151936, 40)
    assert [(r.max_new, r.progress, r.prompt.tolist()) for r in a] == \
        [(r.max_new, r.progress, r.prompt.tolist()) for r in b]
    assert [r.prompt.tolist() for r in a] != [r.prompt.tolist() for r in c]


@pytest.mark.parametrize("path", MIXES, ids=[p.stem for p in MIXES])
def test_mix_respects_its_clips(path):
    mix = _mix(path)
    for seed in (0, 5, 2**32 + 3):
        for r in traffic.generate(mix, seed, 151936, 64):
            assert mix["prompt"]["min"] <= len(r.prompt) - r.progress \
                <= mix["prompt"]["max"]
            assert mix["output"]["min"] <= r.max_new + r.progress \
                <= mix["output"]["max"]
            assert r.max_new >= 2 and r.progress >= 0
            assert len(r.prompt) <= traffic.max_start_prompt(mix)
            assert r.prompt.dtype == np.int32
            assert 0 <= r.prompt.min() and r.prompt.max() < 151936


@pytest.mark.parametrize("path", MIXES, ids=[p.stem for p in MIXES])
def test_every_seed_gets_the_same_work_per_block(path):
    mix = _mix(path)
    n = traffic.STRATA
    runs = [traffic.generate(mix, s, 151936, 2 * n) for s in (1, 2, 3)]
    for blk in range(2):
        for key in (lambda r: len(r.prompt) - r.progress,
                    lambda r: r.max_new + r.progress,
                    lambda r: r.progress):
            sizes = [Counter(key(r) for r in run[blk * n:(blk + 1) * n])
                     for run in runs]
            assert sizes[0] == sizes[1] == sizes[2]


def test_the_first_batch_starts_at_the_steady_state():
    """Outputs picked in proportion to their length, each part-way
    through; the rest of the traffic starts from the beginning."""
    mix = _mix(ROOT / "bench" / "mixes" / "decode.json")
    o = traffic.lengths(mix["output"], traffic.STRATA)
    starts = traffic.steady_starts(o, mix["batch"])
    picked = np.array([out for out, _ in starts])
    share = np.array([d / out for out, d in starts])
    # length-biased: the picks' mean is E[O^2] / E[O] of the strata
    assert picked.mean() == pytest.approx((o ** 2).mean() / o.mean(),
                                          rel=0.05)
    assert 0.3 < share.mean() < 0.7 and share.min() < 0.15 \
        and share.max() > 0.85
    reqs = traffic.generate(mix, 2**31 + 5, 151936, 3 * mix["batch"])
    first, rest = reqs[: mix["batch"]], reqs[mix["batch"]:]
    assert sorted((r.max_new + r.progress, r.progress) for r in first) == \
        sorted(starts)
    assert all(r.progress == 0 for r in rest)


def test_steady_starts_by_hand():
    # strata 4 and 12: 16 positions; points at 2, 6, 10, 14
    assert traffic.steady_starts(np.array([12, 4]), 4) == [
        (4, 2), (12, 2), (12, 6), (12, 10)]
