"""A configuration file -> the served model: program configs, and weights
drawn on the device from the seed.

The weights are the benchmark's own. :func:`make_weights` draws the
target in the benchmark's layout (``bench/reference.py`` reads that
layout) and the two drafters through the program's initializer, all in
one jitted call, in the dtype they are served in. :func:`target_tree`
hands the same target arrays to the program under its parameter names;
nothing is copied.
"""
from __future__ import annotations

import dataclasses
import functools
import json
from pathlib import Path
from typing import Any, Dict

import jax
import jax.numpy as jnp

GAMMA = 16          # draft block length
TOP_K = 4           # second-draft branches


@dataclasses.dataclass(frozen=True)
class Arch:
    """The sizes the reference and the weight generator need."""
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    ff: int
    vocab: int
    eps: float
    theta: float
    tied: bool
    qkv_bias: bool
    qk_norm: bool
    dtype: str

    @classmethod
    def from_file(cls, path: Path) -> "Arch":
        c = json.loads(Path(path).read_text())
        a = c["bench"]["architecture"]
        if c.get("hidden_act", "silu") != "silu":
            raise ValueError(f"{path}: only SwiGLU (silu) MLPs are built")
        return cls(layers=c["num_hidden_layers"], d=c["hidden_size"],
                   heads=c["num_attention_heads"],
                   kv_heads=c["num_key_value_heads"],
                   head_dim=c.get("head_dim") or (c["hidden_size"]
                                                  // c["num_attention_heads"]),
                   ff=c["intermediate_size"], vocab=c["vocab_size"],
                   eps=float(c["rms_norm_eps"]),
                   theta=float(c["rope_theta"]),
                   tied=bool(c["tie_word_embeddings"]),
                   qkv_bias=bool(a["qkv_bias"]), qk_norm=bool(a["qk_norm"]),
                   dtype=c["torch_dtype"])

    def matmul_params(self) -> int:
        """Parameters of the target that a token's forward multiplies by
        (every projection and the head; not the embedding lookup)."""
        q, kv = self.heads * self.head_dim, self.kv_heads * self.head_dim
        per_layer = self.d * (q + 2 * kv) + q * self.d + 3 * self.d * self.ff
        return self.layers * per_layer + self.d * self.vocab


def program_configs(arch: Arch):
    """(target ModelConfig, DrafterConfig, SpecConfig) of the program,
    reading the KV cache through the Pallas cascade kernels."""
    from repro.config.base import Family, ModelConfig, SpecConfig
    from repro.launch.steps import production_drafter
    tcfg = ModelConfig(
        name="bench-target", family=Family.DENSE, num_layers=arch.layers,
        d_model=arch.d, num_heads=arch.heads, num_kv_heads=arch.kv_heads,
        head_dim=arch.head_dim, d_ff=arch.ff, vocab_size=arch.vocab,
        qkv_bias=arch.qkv_bias, qk_norm=arch.qk_norm,
        rope_theta=arch.theta, norm_eps=arch.eps,
        tie_embeddings=arch.tied, max_seq_len=32768, remat=False,
        dtype=arch.dtype, param_dtype=arch.dtype, attn_impl="pallas")
    dcfg = dataclasses.replace(production_drafter(tcfg, GAMMA),
                               attn_impl="pallas")
    spec = SpecConfig(gamma=GAMMA, top_k_branches=TOP_K, mode="d2sd",
                      temperature=0.0)
    return tcfg, dcfg, spec


def _normal(key, shape, std):
    return jax.random.truncated_normal(key, -2.0, 2.0, shape,
                                       jnp.float32) * std


def _target_weights(key, arch: Arch) -> Dict[str, Any]:
    """The target in the benchmark's layout (layers stacked on axis 0),
    drawn in float32 and cast inside the caller's jit."""
    L, d, ff = arch.layers, arch.d, arch.ff
    q, kv = arch.heads * arch.head_dim, arch.kv_heads * arch.head_dim
    ks = iter(jax.random.split(key, 20))
    lay = {
        "attn_norm": 1.0 + _normal(next(ks), (L, d), 0.1),
        "wq": _normal(next(ks), (L, d, q), d ** -0.5),
        "wk": _normal(next(ks), (L, d, kv), d ** -0.5),
        "wv": _normal(next(ks), (L, d, kv), d ** -0.5),
        "wo": _normal(next(ks), (L, q, d), q ** -0.5),
        "mlp_norm": 1.0 + _normal(next(ks), (L, d), 0.1),
        "w_gate": _normal(next(ks), (L, d, ff), d ** -0.5),
        "w_up": _normal(next(ks), (L, d, ff), d ** -0.5),
        "w_down": _normal(next(ks), (L, ff, d), ff ** -0.5),
    }
    if arch.qkv_bias:
        lay["bq"] = _normal(next(ks), (L, q), 0.1)
        lay["bk"] = _normal(next(ks), (L, kv), 0.1)
        lay["bv"] = _normal(next(ks), (L, kv), 0.1)
    if arch.qk_norm:
        lay["q_norm"] = 1.0 + _normal(next(ks), (L, arch.head_dim), 0.1)
        lay["k_norm"] = 1.0 + _normal(next(ks), (L, arch.head_dim), 0.1)
    w = {"embed": _normal(next(ks), (arch.vocab, d), 0.02),
         "final_norm": 1.0 + _normal(next(ks), (d,), 0.1),
         "layers": lay}
    if not arch.tied:
        w["head"] = _normal(next(ks), (d, arch.vocab), 0.02)
    return w


@functools.partial(jax.jit, static_argnums=(1, 2))
def _draw(key, arch: Arch, dcfg):
    from repro.core.drafter import drafter_init
    k_t, k_1, k_2 = jax.random.split(key, 3)
    dt = jnp.dtype(arch.dtype)
    w = jax.tree.map(lambda a: a.astype(dt), _target_weights(k_t, arch))
    return w, drafter_init(k_1, dcfg), drafter_init(k_2, dcfg)


def seed_key(seed: int):
    """A PRNG key from any whole number up to 2**63 (the seed is split
    into two 32-bit words, so large seeds do not overflow)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def make_weights(seed: int, arch: Arch, dcfg):
    """(target weights, drafter-1 params, drafter-2 params), on the
    default device, from ``seed``."""
    return _draw(seed_key(seed), arch, dcfg)


def target_tree(w: Dict[str, Any], arch: Arch) -> Dict[str, Any]:
    """The program's target parameter tree over the same arrays."""
    lay = w["layers"]
    attn = {"wq": lay["wq"], "wk": lay["wk"], "wv": lay["wv"],
            "wo": lay["wo"]}
    if arch.qkv_bias:
        attn.update(bq=lay["bq"], bk=lay["bk"], bv=lay["bv"])
    if arch.qk_norm:
        attn.update(q_norm=lay["q_norm"], k_norm=lay["k_norm"])
    block = {"ln1": {"scale": lay["attn_norm"]}, "attn": attn,
             "ln2": {"scale": lay["mlp_norm"]},
             "ffn": {"w_in": lay["w_up"], "w_gate": lay["w_gate"],
                     "w_out": lay["w_down"]}}
    p = {"tok": {"embedding": w["embed"]},
         "ln_f": {"scale": w["final_norm"]},
         "period": {"p0": block}}
    if not arch.tied:
        p["lm_head"] = w["head"]
    return p


def bundle(arch: Arch, w, d1, d2):
    """The program's SpecBundle over the benchmark's weights."""
    from repro.core.pipeline import SpecBundle
    tcfg, dcfg, spec = program_configs(arch)
    return SpecBundle(tcfg, dcfg, dcfg, spec, target_tree(w, arch), d1, d2)
