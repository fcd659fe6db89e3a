"""The harness finds every part of a cell by name, a cell added as data
only runs, the engine the harness pins is the one it overrides, and the
benchmark refuses to run without a TPU."""
import json
import os
import subprocess
import sys
from types import SimpleNamespace

import pytest

from bench_checkout import ROOT, tiny_checkout
from bench import harness, spec, traffic

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())


def test_every_declared_part_is_a_file_found_by_name():
    for c in BENCH["configs"]:
        assert (ROOT / c["file"]).is_file()
        conf = json.loads((ROOT / c["file"]).read_text())
        assert conf["bench"]["reduced"] == c["reduced"]
        assert conf["bench"]["source"] == c["source"]
        assert "widest_gap" in conf["bench"]["limits"]
    for w in BENCH["workloads"]:
        cell = spec.load_cell(ROOT, w["name"])
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
        assert len(cell.end_to_end) >= 2 and cell.per_layer
    for m in BENCH["per_layer"]:
        assert callable(spec.reader(ROOT, m["name"]))


def test_a_cell_added_as_data_is_found(tmp_path):
    checkout = tiny_checkout(tmp_path)
    cell = spec.load_cell(checkout, "tiny.closed")
    assert cell.mix["batch"] == 4
    assert cell.config_file == checkout / "bench" / "configs" / "tiny.json"
    assert [m["name"] for m in cell.end_to_end] == [
        "setup_s", "tokens_per_s"]
    assert {m["name"] for m in cell.per_layer} == {
        m["name"] for m in BENCH["per_layer"]}
    with pytest.raises(KeyError):
        spec.load_cell(checkout, "no.such.cell")


def test_a_metric_split_by_suffix_is_read_by_its_stem(tmp_path):
    checkout = tiny_checkout(tmp_path)
    stem = spec.reader(checkout, "idle_share")
    run = SimpleNamespace(summary={"idle_share": 0.25})
    assert spec.reader(checkout, "idle_share.decode")(run) == stem(run)
    assert spec.reader(checkout, "idle_share.any_new_suffix")(run) == 25.0
    (checkout / "bench/metrics/idle_share.own.py").write_text(
        "def read(run):\n    return 7.0\n")
    assert spec.reader(checkout, "idle_share.own")(run) == 7.0
    with pytest.raises(FileNotFoundError):
        spec.reader(checkout, "no_such_metric.decode")(run)


def test_install_buckets_follow_the_engine_rule():
    from repro.serving.engine import ServingEngine
    ladder = (16, 32, 64)
    eng = SimpleNamespace(bucket_sizes=ladder)
    for n in (1, 16, 17, 64, 65, 130):
        assert harness.bucket(n, ladder) == ServingEngine._bucket(eng, n)
    assert harness.pads(8, 40, ladder) == [16, 32, 64]


def _run_py(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(ROOT / "src"))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "qwen2.5-3b.decode",
         "--seed", "1", "--seconds", "1", "--trace", "0", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_refuses_a_machine_without_a_tpu():
    p = _run_py(ROOT)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert '"metrics"' not in p.stdout


def test_run_fails_in_a_checkout_of_the_benchmark_alone(tmp_path):
    import shutil
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "qwen2.5-3b.decode",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert '"metrics"' not in p.stdout


def test_peaks_refuse_an_unknown_device():
    from bench.peaks import peaks
    assert peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        peaks("some other chip")


def test_mixes_and_longest_shapes():
    mix = json.loads((ROOT / "bench/mixes/decode.json").read_text())
    assert traffic.max_prompt(mix) == 1536 and traffic.max_new(mix) == 2048
