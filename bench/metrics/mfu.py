"""Model step: FLOPs of the plain target forward for the tokens committed
in the window, per second, over the chip's bf16 peak (%)."""
from bench.readers import target_flops


def read(run):
    if not run.cycles:
        return None
    return (100.0 * target_flops(run, run.cycles) / run.window_s
            / run.peak["bf16_flops"])
