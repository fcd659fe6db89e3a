"""A decoder whose layers follow a repeating pattern of sliding-window and
global attention, as an architecture module (what one provides:
``bench/model.py``). The configuration file lists each layer's kind in
``layer_types`` (``sliding_attention`` or ``full_attention``) and the
window in ``sliding_window``: a sliding-window layer at position p reads
positions p - window + 1 .. p.

Each layer is ``bench/model.py``'s dense layer. The weights of the layers
at each place of the pattern are stacked apart (``layers["p<j>"]``,
``num_hidden_layers / len(pattern)`` each), as the program stacks them;
its plain forward is ``tiny_window_reference.py``.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from typing import Any, Dict, Tuple

import jax

from bench import model

KINDS = {"sliding_attention": "local", "full_attention": "global"}


@dataclasses.dataclass(frozen=True)
class Arch(model.Arch):
    window: int
    pattern: Tuple[str, ...]    # one period of layer kinds: local | global

    @classmethod
    def from_file(cls, path: Path) -> "Arch":
        c = json.loads(Path(path).read_text())
        kinds = tuple(KINDS[t] for t in c["layer_types"])
        dense = model.Arch.from_file(path)
        if len(kinds) != dense.layers:
            raise ValueError(f"{path}: {len(kinds)} layer_types for "
                             f"{dense.layers} layers")
        period = next(n for n in range(1, len(kinds) + 1)
                      if kinds == kinds[:n] * (len(kinds) // n))
        return cls(**dataclasses.asdict(dense),
                   window=int(c["sliding_window"]), pattern=kinds[:period])

    def attended(self, ctx: int) -> int:
        """Tokens the attention layers read at context ``ctx``: a
        sliding-window layer reads ``min(ctx, window)``."""
        per = sum(min(ctx, self.window) if k == "local" else ctx
                  for k in self.pattern)
        return self.layers // len(self.pattern) * per


def program_configs(arch: Arch):
    tcfg = dataclasses.replace(model.target_config(arch),
                               layer_pattern=arch.pattern,
                               sliding_window=arch.window)
    return model.spec_configs(tcfg)


def _target_weights(key, arch: Arch) -> Dict[str, Any]:
    n = arch.layers // len(arch.pattern)
    ks = iter(jax.random.split(key, 14 * len(arch.pattern) + 3))
    w = {"layers": {f"p{j}": model.layer_stack(ks, n, arch)
                    for j in range(len(arch.pattern))},
         "embed": model.normal(next(ks), (arch.vocab, arch.d), 0.02),
         "final_norm": 1.0 + model.normal(next(ks), (arch.d,), 0.1)}
    if not arch.tied:
        w["head"] = model.normal(next(ks), (arch.d, arch.vocab), 0.02)
    return w


def make_weights(seed: int, arch: Arch, dcfg):
    return model.draw_weights(model.seed_key(seed), arch, dcfg,
                              _target_weights)


def bundle(arch: Arch, w, d1, d2):
    from repro.core.pipeline import SpecBundle
    tcfg, dcfg, spec = program_configs(arch)
    p = {"tok": {"embedding": w["embed"]},
         "ln_f": {"scale": w["final_norm"]},
         "period": {j: model.block_tree(lay, arch)
                    for j, lay in w["layers"].items()}}
    if not arch.tied:
        p["lm_head"] = w["head"]
    return SpecBundle(tcfg, dcfg, dcfg, spec, p, d1, d2)
