"""Record the small device trace that ``test_bench_trace.py`` reads.

    python3 tests/bench/record_trace.py OUT_DIR      # on one TPU chip

Runs a few small programs (a matrix product and a Pallas kernel) under
the benchmark's ``bench.*`` spans, with host sleeps between them so the
device has idle gaps of known cause, and writes the trace's compact JSON
(``bench.trace_reduce.save_json``) to OUT_DIR.
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

from bench import trace_reduce  # noqa: E402


def _double_kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...] * 2.0


@jax.jit
def double(x):
    return pl.pallas_call(_double_kernel,
                          out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype))(x)


@jax.jit
def matmul(a):
    return a @ a


def main(out: Path) -> None:
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("record_trace: needs a TPU")
    a = jnp.ones((2048, 2048), jnp.bfloat16)
    x = jnp.ones((512, 512), jnp.float32)
    matmul(a).block_until_ready()
    double(x).block_until_ready()
    tmp = out / "xplane_tmp"
    jax.profiler.start_trace(str(tmp))
    for _ in range(3):
        with jax.profiler.TraceAnnotation("bench.dispatch_cycle"):
            y = matmul(a)
        with jax.profiler.TraceAnnotation("bench.complete_cycle"):
            y.block_until_ready()
        with jax.profiler.TraceAnnotation("bench.wait"):
            time.sleep(0.002)
        with jax.profiler.TraceAnnotation("bench.admit_idle"):
            double(x).block_until_ready()
    jax.profiler.stop_trace()
    path = sorted(tmp.rglob("*.xplane.pb"))[-1]
    trace = trace_reduce.load_xplane(path)
    trace_reduce.save_json(trace, out / "recorded_trace.json")
    shutil.rmtree(tmp)
    s = trace_reduce.summarize(trace)
    print({k: s[k] for k in ("busy_s", "window_s", "idle_share")})
    print(sorted({n for n, _, _ in trace["ops"]}))
    print(sorted({n for n, _, _ in trace["modules"]}))


if __name__ == "__main__":
    main(Path(sys.argv[1]))
