"""Record the small device trace that ``test_bench_scopes.py`` reads.

    python3 tests/bench/record_trace_scoped.py OUT_DIR      # on one TPU chip

Runs a small program named ``decode_cycle`` with the decode cycle's
``d2sd.*`` scopes (a matrix product in each draft scope, a top-k in
``d2sd.select``, a layer scan over a Pallas kernel named
``cascade_read_paged`` with the tree verify's partials shape in
``d2sd.verify``, a sum in ``d2sd.commit``), three times, under the
benchmark's ``bench.*`` spans and the engine's ``engine.*`` spans, with
host sleeps in ``engine.bank`` and ``engine.admit_idle`` so that the
device has idle gaps of known cause. Writes the trace's compact JSON
(``bench.xplane.save_json``: the benchmark's three lists plus ``scopes``
and ``program_spans``) to OUT_DIR.
"""
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.experimental import pallas as pl  # noqa: E402

from bench import cycle_trace, trace_reduce, xplane  # noqa: E402
from repro.serving.spans import span  # noqa: E402

B, HQ, T, D = 2, 4, cycle_trace.TREE_NODES, 128
LAYERS = 3
BANK_S, ADMIT_S = 0.002, 0.001


def _read_kernel(q_ref, acc_ref, m_ref, l_ref):
    acc_ref[...] = q_ref[...] * 2.0
    m_ref[...] = jnp.zeros(m_ref.shape, jnp.float32)
    l_ref[...] = jnp.ones(l_ref.shape, jnp.float32)


def read(q):
    """[B, HQ, 1, T, D] -> partials shaped like the paged cascade read's."""
    shapes = [jax.ShapeDtypeStruct((B, HQ, 1, T, n), jnp.float32)
              for n in (D, 1, 1)]
    return pl.pallas_call(_read_kernel, name=cycle_trace.KERNEL,
                          out_shape=shapes)(q)


@jax.jit
def decode_cycle(q, w):
    with jax.named_scope("d2sd.draft1"):
        a = jnp.tanh(w @ w)
    with jax.named_scope("d2sd.select"):
        _, top = jax.lax.top_k(a[0].astype(jnp.float32), 4)
    with jax.named_scope("d2sd.draft2"):
        b = jnp.tanh(a @ w)

    def layer(c, _):
        acc, m, l = read(c)
        return c + 1e-3 * acc + m + l, None
    with jax.named_scope("d2sd.verify"):
        y, _ = jax.lax.scan(layer, q, None, length=LAYERS)
    with jax.named_scope("d2sd.commit"):
        return y.sum() + b.astype(jnp.float32).sum() + top.sum()


def main(out: Path) -> None:
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("record_trace_scoped: needs a TPU")
    q = jnp.ones((B, HQ, 1, T, D), jnp.float32)
    w = jnp.full((1024, 1024), 0.01, jnp.bfloat16)
    decode_cycle(q, w).block_until_ready()
    acc = {}
    tmp = out / "xplane_scoped_tmp"
    jax.profiler.start_trace(str(tmp))
    for i in range(3):
        with jax.profiler.TraceAnnotation("bench.dispatch_cycle"):
            with span(acc, "engine.dispatch_cycle", cycle=i):
                with span(acc, "engine.enqueue"):
                    y = decode_cycle(q, w)
        with jax.profiler.TraceAnnotation("bench.complete_cycle"):
            with span(acc, "engine.complete_cycle", cycle=i):
                with span(acc, "engine.readback"):
                    y.block_until_ready()
                with span(acc, "engine.bank"):
                    time.sleep(BANK_S)
        with jax.profiler.TraceAnnotation("bench.admit_idle"):
            with span(acc, "engine.admit_idle"):
                time.sleep(ADMIT_S)
    jax.profiler.stop_trace()
    path = sorted(tmp.rglob("*.xplane.pb"))[-1]
    trace = xplane.load(path)
    xplane.save_json(trace, out / "recorded_trace_scoped.json")
    shutil.rmtree(tmp)
    lo, hi = trace_reduce.window(trace)
    print({k: v for k, v in cycle_trace.phase_ms(trace, lo, hi).items()})
    print(cycle_trace.verify_read(trace["ops"], HQ, lo, hi))
    print(cycle_trace.idle_by_span(trace, lo, hi))
    print(cycle_trace.idle_covered_ns(trace, lo, hi))
    print(sorted({s for s in trace["scopes"]}))
    print(sorted({n for n, _, _ in trace["modules"]}))


if __name__ == "__main__":
    main(Path(sys.argv[1]))
