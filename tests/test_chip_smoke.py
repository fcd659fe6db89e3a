"""chip_smoke.py: its phases at a tiny size on the CPU, and its refusal to
run anywhere but a TPU."""
import os
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np

from conftest import tiny_drafter, tiny_target
from repro.config.base import SpecConfig
from repro.launch.serve import random_bundle

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402


def _bundle():
    tcfg = tiny_target(vocab=97, attn_impl="pallas")
    dcfg = tiny_drafter(vocab=97, gamma=4, target_cfg=tcfg,
                        attn_impl="pallas")
    spec = SpecConfig(gamma=4, top_k_branches=2, mode="d2sd")
    return random_bundle(tcfg, dcfg, spec, seed=3)


def test_serve_phase_matches_greedy_reference():
    """The one-chip phase at tiny widths: the engine (paged pool, cascade
    kernels in interpret mode) agrees with the greedy reference."""
    prompts = np.random.default_rng(0).integers(
        3, 97, size=(3, 20)).astype(np.int32)
    timer = chip_smoke.CompileTimer()
    assert chip_smoke.one_chip_phase(_bundle(), prompts, 9, 16, timer)
    assert timer.s > 0


def test_greedy_reference_matches_plain_cached_decode():
    """The reference's paged gather read + commit step equals plain cached
    greedy decoding over dense buffers (conftest.pure_greedy), whatever the
    bundle's read path."""
    from conftest import pure_greedy
    bundle = _bundle()
    prompts = np.random.default_rng(1).integers(
        3, 97, size=(2, 13)).astype(np.int32)
    toks, gap, finite = chip_smoke.greedy_reference(bundle, prompts, 7, 16)
    want = pure_greedy(bundle.target_params, bundle.target_cfg,
                       jax.numpy.asarray(prompts), 7)
    np.testing.assert_array_equal(toks, np.asarray(want))
    assert finite and gap.shape == (2, 7) and (gap >= 0).all()


def test_compare_near_tie_rule(capsys):
    ref = np.array([[5, 6, 7], [1, 2, 3]])
    gap = np.array([[1.0, 1.0, 1.0], [1.0, 5e-3, 1.0]])
    near_tie = np.array([[5, 6, 7], [1, 9, 9]])
    assert chip_smoke.compare("t", near_tie, ref, lambda i, j: gap[i, j])
    assert "near-tie" in capsys.readouterr().out
    wrong = np.array([[5, 0, 7], [1, 2, 3]])
    assert not chip_smoke.compare("t", wrong, ref, lambda i, j: gap[i, j])
    assert "MISMATCH" in capsys.readouterr().out


def test_refuses_to_run_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                         env=env, cwd=str(ROOT), capture_output=True,
                         text=True, timeout=300)
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout
    assert "no TPU" in out.stderr


def test_compile_cache_location(monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR wins and nothing is set in code; unset, the
    cache goes to the fixed directory in the checkout."""
    from repro.launch import compile_cache
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setattr(compile_cache, "DEFAULT_DIR", tmp_path / "cache")
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "env"))
        assert compile_cache.enable_compile_cache() == str(tmp_path / "env")
        assert jax.config.jax_compilation_cache_dir == before
        assert not (tmp_path / "cache").exists()
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert compile_cache.enable_compile_cache() == str(tmp_path / "cache")
        assert jax.config.jax_compilation_cache_dir == str(tmp_path / "cache")
        assert (tmp_path / "cache").is_dir()
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
