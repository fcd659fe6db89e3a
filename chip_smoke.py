"""Chip smoke test: serve qwen2.5-3b at full width through the D2SD engine.

    python chip_smoke.py              # one TPU chip
    python chip_smoke.py --chips 4    # the kv_seq-sharded engine, 4 chips

Every phase runs in this one process: a chip belongs to one process.

One chip: qwen2.5-3b at its published widths with random bfloat16
weights and two production drafters (gamma 16, K 4), all drawn from
``--seed``, serve ``SyntheticDataset`` requests through ``ServingEngine``
with the paged KV pool (page 64) and the compiled cascade kernels
(``attn_impl="pallas"``). The served tokens are checked against a plain
greedy decode of the same target (one prefill, then one-token steps) over
the same paged cache, read through the plain gather path, so the compiled
kernels are checked against code that does not use them.

``--chips 4``: the same requests through one engine spanning a 1x4
(data, model) mesh with the KV pool's ``kv_seq`` axis on ``model``, built
by ``launch.serve.mesh_context`` as ``serve.py --mesh-model 4`` builds it
(the engine places the weights on the mesh), checked against a
single-device engine on device 0 in this process; the pool's bytes are
checked to sit on all four devices, and the weights on each. No other
phase.

A served token that differs from its reference fails the run, unless the
reference's top-2 logit gap at that position is under ``NEAR_TIE`` (a
bfloat16 near-tie), which is reported; the tokens after it are not
compared. Lines before the last are for the record only. The last line is
one JSON object, ``{"ok": true, "device": {...}}``; the script exits
non-zero without it when JAX finds no TPU or any phase fails.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import functools
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import pipeline as pl  # noqa: E402
from repro.data.synthetic import SyntheticDataset  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.serve import arch_bundle, mesh_context  # noqa: E402
from repro.models import lm  # noqa: E402
from repro.serving.engine import ServingEngine  # noqa: E402

ARCH = "qwen2.5-3b"
PAGE = 64
N_REQ = 4
PROMPT_LEN = 256
MAX_NEW = 64
NEAR_TIE = 1e-2


def log(msg: str) -> None:
    print(msg, flush=True)


class CompileTimer:
    """Seconds JAX spends tracing, lowering and compiling (or fetching a
    compiled program from the persistent cache)."""

    def __init__(self):
        self.s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event.startswith("/jax/core/compile/"):
            self.s += duration


def tree_bytes(tree) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


def serve(bundle, prompts, max_new, page_size, mesh_ctx=None):
    """Serve ``prompts`` through one paged ``ServingEngine`` built inside
    ``mesh_ctx``; returns (tokens [n, max_new] in submit order, stats,
    engine)."""
    with mesh_ctx or contextlib.nullcontext():
        eng = ServingEngine(bundle, batch_size=len(prompts),
                            cache_impl="paged", page_size=page_size)
    for p in prompts:
        eng.submit(p, max_new=max_new)
    stats = eng.run()
    done = sorted(eng.done, key=lambda r: r.uid)
    lens = [len(r.out) for r in done]
    if len(done) != len(prompts) or any(n != max_new for n in lens):
        raise RuntimeError(f"served {len(done)}/{len(prompts)} requests "
                           f"with {lens} tokens, wanted {max_new} each")
    return np.stack([r.out for r in done]), stats, eng


def greedy_reference(bundle, prompts, n, page_size, read_impl="gather"):
    """Plain greedy decode of the target: prefill, then ``n - 1`` one-token
    steps, each reading the paged cache through ``read_impl`` (the plain
    gather path by default, whatever the bundle's ``attn_impl``) and
    committing its token's KV, as the engine's verify does. Returns
    (tokens [B, n], top-2 logit gap [B, n], all logits finite)."""
    cfg = dataclasses.replace(bundle.target_cfg, attn_impl=read_impl)
    prompts = jnp.asarray(prompts)
    b, p = prompts.shape
    states = lm.init_states(cfg, b, p + n, dtype=jnp.dtype(cfg.dtype),
                            cache_impl="paged", page_size=page_size)

    def pick(logits):
        logits = logits[:, -1].astype(jnp.float32)
        top = jax.lax.top_k(logits, 2)[0]
        return (jnp.argmax(logits, -1).astype(jnp.int32),
                top[:, 0] - top[:, 1], jnp.isfinite(logits).all())

    @jax.jit
    def prefill(tp, states, prompts):
        out = lm.forward(tp, prompts, cfg, states=states, write_kv=True,
                         remat=False)
        return (out["states"],) + pick(out["logits"])

    @functools.partial(jax.jit, donate_argnums=1)
    def step(tp, states, tok):
        out = lm.forward(tp, tok[:, None], cfg, states=states, remat=False)
        states = lm.commit_kv(states, out["kv_outs"], cfg,
                              jnp.zeros((b, 1), jnp.int32),
                              jnp.ones((b,), jnp.int32))
        return (states,) + pick(out["logits"])

    tp = bundle.target_params
    states, tok, gap, finite = prefill(tp, states, prompts)
    toks, gaps, fins = [tok], [gap], [finite]
    for _ in range(n - 1):
        states, tok, gap, finite = step(tp, states, tok)
        toks.append(tok)
        gaps.append(gap)
        fins.append(finite)
    return (np.asarray(jnp.stack(toks, 1)), np.asarray(jnp.stack(gaps, 1)),
            bool(np.all([bool(f) for f in fins])))


def compare(name, got, ref, gap_of) -> bool:
    """Token check under the near-tie rule. ``gap_of(i, j)`` gives the
    reference's top-2 logit gap for request ``i`` at position ``j``."""
    ok = True
    for i in range(len(ref)):
        diff = np.nonzero(got[i] != ref[i])[0]
        if not len(diff):
            continue
        j = int(diff[0])
        g = float(gap_of(i, j))
        what = "near-tie, not failed" if g < NEAR_TIE else "MISMATCH"
        log(f"{name}: request {i} first differs at position {j} "
            f"(served {got[i, j]}, reference {ref[i, j]}); reference top-2 "
            f"logit gap {g:.3g}: {what}")
        ok &= g < NEAR_TIE
    exact = sum(bool(np.array_equal(a, b)) for a, b in zip(got, ref))
    log(f"{name}: {exact}/{len(ref)} requests token-identical")
    return ok


def one_chip_phase(bundle, prompts, max_new, page_size, timer) -> bool:
    c0, t0 = timer.s, time.perf_counter()
    got, stats, _ = serve(bundle, prompts, max_new, page_size)
    log(f"serve: wall_s={time.perf_counter() - t0:.3f} "
        f"compile_s={timer.s - c0:.3f} warm_cycle_s={stats['warm_cycle_s']} "
        f"alpha={stats['alpha']:.4f} cycles={stats['cycles']} "
        f"tokens={stats['tokens']}")
    c0 = timer.s
    ref, gap, finite = greedy_reference(bundle, prompts, max_new, page_size)
    log(f"reference: compile_s={timer.s - c0:.3f} logits_finite={finite}")
    return compare("serve vs greedy", got, ref, lambda i, j: gap[i, j]) \
        and finite


def sharded_phase(bundle, prompts, max_new, page_size, timer) -> bool:
    c0 = timer.s
    single, _, _ = serve(bundle, prompts, max_new, page_size)
    log(f"single-device engine: compile_s={timer.s - c0:.3f}")

    c0 = timer.s
    got, stats, eng = serve(bundle, prompts, max_new, page_size,
                            mesh_context(1, 4, kv_seq_axis="model"))
    log(f"sharded engine: compile_s={timer.s - c0:.3f} "
        f"kv_shards={stats['kv_shards']} "
        f"pool_shard_slots={stats['pool_shard_slots']} "
        f"warm_cycle_s={stats['warm_cycle_s']} cycles={stats['cycles']}")

    weight_devs = {len(x.devices()) for x in jax.tree.leaves(eng.bundle)}
    log(f"engine weights: on {sorted(weight_devs)} devices per leaf")
    spread = True
    for name, (k, _) in eng._pools.items():
        devs = {s.device for s in k.addressable_shards}
        shard_shape = k.addressable_shards[0].data.shape
        log(f"pool {name}: shape {k.shape} on {len(devs)} devices, "
            f"shard {shard_shape}")
        spread &= len(devs) == 4 and shard_shape[-3] == k.shape[-3] // 4
    if not spread:
        log("pool bytes are not split over the 4 devices")

    ref = {}

    def gap_of(i, j):
        # the single-device tokens are the reference; the greedy decode
        # that gives their logit gaps (through the engine's own read path,
        # so that it follows the same tokens) runs only when tokens differ
        if not ref:
            toks, gap, _ = greedy_reference(
                bundle, prompts, max_new, page_size,
                read_impl=bundle.target_cfg.attn_impl)
            ref.update(toks=toks, gap=gap)
        same = np.array_equal(ref["toks"][i, :j + 1], single[i, :j + 1])
        return ref["gap"][i, j] if same else np.inf

    return compare("sharded vs single-device", got, single, gap_of) \
        and spread and weight_devs == {4}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the kv_seq-sharded phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: JAX found no TPU (platform {dev.platform!r})")
    if len(devs) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} but {len(devs)} visible")
    log(f"device: {dev.platform} {dev.device_kind} x{len(devs)}")
    log(f"compile cache: {enable_compile_cache()}")
    timer = CompileTimer()

    t0 = time.perf_counter()
    bundle = pl.with_attn_impl(arch_bundle(ARCH, gamma=16, k=4,
                                           seed=args.seed), "pallas")
    jax.block_until_ready(bundle)
    log(f"init: {ARCH} s={time.perf_counter() - t0:.3f} "
        f"target_param_bytes={tree_bytes(bundle.target_params)} "
        f"drafter_param_bytes="
        f"{tree_bytes(bundle.d1_params) + tree_bytes(bundle.d2_params)}")
    prompts = SyntheticDataset("math", 1, PROMPT_LEN, seed=args.seed
                               ).prompts(N_REQ, PROMPT_LEN)

    phase = sharded_phase if args.chips == 4 else one_chip_phase
    ok = phase(bundle, prompts, MAX_NEW, PAGE, timer)
    stats = dev.memory_stats() or {}
    log(f"peak_bytes_in_use={stats.get('peak_bytes_in_use')} "
        f"compile_s_total={timer.s:.3f}")
    if not ok:
        sys.exit("chip_smoke: FAILED")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))


if __name__ == "__main__":
    main()
