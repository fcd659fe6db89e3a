"""The harness finds every part of a cell by name, a cell added as data
only runs, the engine the harness pins is the one it overrides, and the
benchmark refuses to run without a TPU. A configuration names its
architecture or is served by the dense one, whose weights and arithmetic
stay as they were."""
import hashlib
import json
import os
import re
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench_checkout import DATA, ROOT, tiny_checkout
from bench import cycle_trace, harness, model, readers, reference, spec
from bench import traffic

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())


def _provides_the_contract(path, arch_mod, ref_mod):
    """The names the harness and the readers call on an architecture's two
    modules."""
    assert Path(arch_mod.__file__).is_file()
    assert Path(ref_mod.__file__).is_file()
    for name in ("program_configs", "make_weights", "bundle"):
        assert callable(getattr(arch_mod, name))
    assert callable(ref_mod.tail_logits)
    arch = arch_mod.Arch.from_file(path)
    assert hash(arch) == hash(arch_mod.Arch.from_file(path))
    assert min(arch.vocab, arch.layers, arch.heads, arch.kv_heads,
               arch.head_dim, arch.matmul_params()) > 0
    assert 0 < arch.attended(1) <= arch.attended(4096)


def test_every_declared_part_is_a_file_found_by_name():
    for c in BENCH["configs"]:
        assert (ROOT / c["file"]).is_file()
        conf = json.loads((ROOT / c["file"]).read_text())
        assert conf["bench"]["reduced"] == c["reduced"]
        assert conf["bench"]["source"] == c["source"]
        assert "widest_gap" in conf["bench"]["limits"]
        _provides_the_contract(ROOT / c["file"],
                               *spec.arch_modules(ROOT, ROOT / c["file"]))
    for w in BENCH["workloads"]:
        cell = spec.load_cell(ROOT, w["name"])
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
        assert len(cell.end_to_end) >= 2 and cell.per_layer
    for m in BENCH["per_layer"]:
        assert callable(spec.reader(ROOT, m["name"]))


@pytest.mark.parametrize("name,config,arch_file", [
    ("tiny.closed", "tiny.json", None),
    ("tiny-window.closed", "tiny-window.json", "tiny_window")])
def test_a_cell_added_as_data_is_found(tmp_path, name, config, arch_file):
    checkout = tiny_checkout(tmp_path)
    cell = spec.load_cell(checkout, name)
    assert cell.mix["batch"] == 4
    assert cell.config_file == checkout / "bench" / "configs" / config
    assert [m["name"] for m in cell.end_to_end] == [
        "setup_s", "tokens_per_s"]
    assert {m["name"] for m in cell.per_layer} == {
        m["name"] for m in BENCH["per_layer"]}
    if arch_file is None:                       # the dense default
        assert (cell.model, cell.reference) == (model, reference)
    else:
        archs = checkout / "bench" / "archs"
        assert Path(cell.model.__file__) == archs / f"{arch_file}.py"
        assert Path(cell.reference.__file__) == \
            archs / f"{arch_file}_reference.py"
    _provides_the_contract(cell.config_file, cell.model, cell.reference)
    with pytest.raises(KeyError):
        spec.load_cell(checkout, "no.such.cell")


def test_a_configuration_without_a_model_is_served_by_the_dense_modules():
    for c in BENCH["configs"] + [{"file": "tests/bench/data/tiny.json"}]:
        assert spec.arch_files(ROOT, ROOT / c["file"]) is None
        mods = spec.arch_modules(ROOT, ROOT / c["file"])
        assert [Path(m.__file__) for m in mods] == [
            ROOT / "bench" / "model.py", ROOT / "bench" / "reference.py"]


def test_a_named_architecture_that_is_missing_is_an_error_with_its_path(
        tmp_path):
    checkout = tiny_checkout(tmp_path)
    conf_file = checkout / "bench" / "configs" / "tiny-window.json"
    conf = json.loads(conf_file.read_text())
    archs = checkout / "bench" / "archs"
    (archs / "tiny_window_reference.py").unlink()
    with pytest.raises(FileNotFoundError,
                       match=re.escape(str(archs /
                                           "tiny_window_reference.py"))):
        spec.load_cell(checkout, "tiny-window.closed")
    conf["bench"]["model"] = "no_such_arch"
    conf_file.write_text(json.dumps(conf))
    with pytest.raises(FileNotFoundError,
                       match=re.escape(str(archs / "no_such_arch.py"))):
        spec.load_cell(checkout, "tiny-window.closed")
    conf["bench"]["model"] = "../metrics/mfu"
    conf_file.write_text(json.dumps(conf))
    with pytest.raises(ValueError, match="is not a name"):
        spec.load_cell(checkout, "tiny-window.closed")


def _tree_hash(tree) -> str:
    import jax
    import numpy as np
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        a = np.asarray(leaf)
        h.update(f"{jax.tree_util.keystr(path)}{a.shape}{a.dtype}".encode())
        h.update(a.tobytes())
    return h.hexdigest()[:16]


def test_the_same_seed_draws_the_same_dense_weights_as_before():
    """sha256 prefixes of the target and the two drafters, drawn on the CPU
    by the dense module before architectures could be named."""
    arch = model.Arch.from_file(DATA / "tiny.json")
    _, dcfg, _ = model.program_configs(arch)
    for seed, want in (
            (1, ("41f05215cee9ce06", "bc8253aefe6244ab",
                 "e28d5138bc46c3f0")),
            (2**33 + 5, ("a324b2cab8ac4704", "73bc9062e33e7d7d",
                         "34e78322e04d5ee0"))):
        got = tuple(_tree_hash(t)
                    for t in model.make_weights(seed, arch, dcfg))
        assert got == want, seed


@pytest.mark.parametrize("config", [
    DATA / "tiny.json", ROOT / "bench/configs/qwen2.5-3b.json",
    ROOT / "bench/configs/paper-target-12l.json"])
def test_the_dense_arch_gives_the_flops_and_read_work_of_before(config):
    """``mfu``'s and ``cascade_read_roofline``'s arithmetic through
    ``Arch.matmul_params`` and ``Arch.attended`` against the dense formulas
    they replaced: layers x heads x head_dim x context."""
    from bench.driver import Cycle
    a = model.Arch.from_file(config)
    lens = [1, 127, 1263, 2047, 3583]
    cycles = [Cycle(0.0, 0.1, rows=16, lens=lens, n_out=[1, 16, 3, 7, 16],
                    pool_use=0.5)]
    q, kv = a.heads * a.head_dim, a.kv_heads * a.head_dim
    params = a.layers * (a.d * (q + 2 * kv) + q * a.d + 3 * a.d * a.ff) \
        + a.d * a.vocab
    assert a.matmul_params() == params
    flops = sum(n * (2.0 * params + 4.0 * a.layers * q * ln)
                for n, ln in zip(cycles[0].n_out, lens))
    got = readers.target_flops(SimpleNamespace(arch=a), cycles)
    assert got == pytest.approx(flops, rel=1e-12)
    ctx = float(sum(lens))
    want = (a.layers * 4.0 * q * cycle_trace.TREE_NODES * ctx,
            a.layers * 2.0 * kv * 2 * ctx)
    assert cycle_trace.read_work(a, lens) == pytest.approx(want, rel=1e-12)


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
