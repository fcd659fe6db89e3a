"""The dense architecture module: a configuration file -> the served model
(program configs, and weights drawn on the device from the seed).

A configuration file names its architecture in its ``bench`` block
(``"model": "<name>"``); without that key this module and
``bench/reference.py`` serve it. A named architecture is
``bench/archs/<name>.py`` with its plain forward in
``bench/archs/<name>_reference.py``, both loaded by path
(``bench/spec.py``), so a later PR adds one as new files. An architecture
module provides what this one does:

* ``Arch.from_file(path)``: the sizes, hashable (a static argument of
  jitted calls), with ``vocab``, ``layers``, ``heads``, ``kv_heads`` and
  ``head_dim``; ``Arch.matmul_params()`` and ``Arch.attended(ctx)``, the
  two quantities the per-layer readers need (``mfu``,
  ``cascade_read_roofline``);
* ``program_configs(arch)``: (target ModelConfig, DrafterConfig,
  SpecConfig) of the program;
* ``make_weights(seed, arch, dcfg)``: (target weights in the benchmark's
  layout, drafter-1 params, drafter-2 params), on the device;
* ``bundle(arch, w, d1, d2)``: the program's ``SpecBundle`` over them.

The comparison that decides ``correct`` is shared by every architecture
(``bench/reference.py``). ``GAMMA`` and ``TOP_K`` are the engine's
geometry, not the architecture's. :func:`target_config`,
:func:`spec_configs`, :func:`normal`, :func:`layer_stack`,
:func:`draw_weights`, :func:`seed_key` and :func:`block_tree` are pieces
another architecture module can reuse.

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

    def attended(self, ctx: int) -> int:
        """Tokens the attention layers read at context ``ctx``, summed over
        layers: every layer attends to the whole context."""
        return self.layers * ctx


def target_config(arch: Arch):
    """The program's ModelConfig of the dense target, reading the KV cache
    through the Pallas cascade kernels."""
    from repro.config.base import Family, ModelConfig
    return ModelConfig(
        name="bench-target", family=Family.DENSE, num_layers=arch.layers,
        d_model=arch.d, num_heads=arch.heads, num_kv_heads=arch.kv_heads,
        head_dim=arch.head_dim, d_ff=arch.ff, vocab_size=arch.vocab,
        qkv_bias=arch.qkv_bias, qk_norm=arch.qk_norm,
        rope_theta=arch.theta, norm_eps=arch.eps,
        tie_embeddings=arch.tied, max_seq_len=32768, remat=False,
        dtype=arch.dtype, param_dtype=arch.dtype, attn_impl="pallas")


def spec_configs(tcfg):
    """(tcfg, DrafterConfig, SpecConfig): the benchmark's drafters and
    greedy D2SD cycle for the target ``tcfg``."""
    from repro.config.base import SpecConfig
    from repro.launch.steps import production_drafter
    dcfg = dataclasses.replace(production_drafter(tcfg, GAMMA),
                               attn_impl="pallas")
    spec = SpecConfig(gamma=GAMMA, top_k_branches=TOP_K, mode="d2sd",
                      temperature=0.0)
    return tcfg, dcfg, spec


def program_configs(arch: Arch):
    """(target ModelConfig, DrafterConfig, SpecConfig) of the program."""
    return spec_configs(target_config(arch))


def normal(key, shape, std):
    """A float32 draw of ``shape`` from a normal of ``std`` cut at two
    standard deviations."""
    return jax.random.truncated_normal(key, -2.0, 2.0, shape,
                                       jnp.float32) * std


def layer_stack(ks, n: int, arch: Arch) -> Dict[str, Any]:
    """``n`` dense layers stacked on axis 0, each weight drawn from the
    next key of the iterator ``ks`` (nine, three more with q/k/v biases,
    two more with qk_norm)."""
    d, ff = arch.d, arch.ff
    q, kv = arch.heads * arch.head_dim, arch.kv_heads * arch.head_dim
    lay = {
        "attn_norm": 1.0 + normal(next(ks), (n, d), 0.1),
        "wq": normal(next(ks), (n, d, q), d ** -0.5),
        "wk": normal(next(ks), (n, d, kv), d ** -0.5),
        "wv": normal(next(ks), (n, d, kv), d ** -0.5),
        "wo": normal(next(ks), (n, q, d), q ** -0.5),
        "mlp_norm": 1.0 + normal(next(ks), (n, d), 0.1),
        "w_gate": normal(next(ks), (n, d, ff), d ** -0.5),
        "w_up": normal(next(ks), (n, d, ff), d ** -0.5),
        "w_down": normal(next(ks), (n, ff, d), ff ** -0.5),
    }
    if arch.qkv_bias:
        lay["bq"] = normal(next(ks), (n, q), 0.1)
        lay["bk"] = normal(next(ks), (n, kv), 0.1)
        lay["bv"] = normal(next(ks), (n, kv), 0.1)
    if arch.qk_norm:
        lay["q_norm"] = 1.0 + normal(next(ks), (n, arch.head_dim), 0.1)
        lay["k_norm"] = 1.0 + normal(next(ks), (n, arch.head_dim), 0.1)
    return lay


def _target_weights(key, arch: Arch) -> Dict[str, Any]:
    """The target in the benchmark's layout (layers stacked on axis 0),
    drawn in float32 and cast inside the caller's jit."""
    ks = iter(jax.random.split(key, 20))
    lay = layer_stack(ks, arch.layers, arch)
    w = {"embed": normal(next(ks), (arch.vocab, arch.d), 0.02),
         "final_norm": 1.0 + normal(next(ks), (arch.d,), 0.1),
         "layers": lay}
    if not arch.tied:
        w["head"] = normal(next(ks), (arch.d, arch.vocab), 0.02)
    return w


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def draw_weights(key, arch, dcfg, target_weights):
    """(target, drafter-1 params, drafter-2 params) from ``key`` in one
    jitted call: the target drawn by ``target_weights(key, arch)`` in
    float32 and cast to ``arch.dtype``, the drafters by the program's
    initializer."""
    from repro.core.drafter import drafter_init
    k_t, k_1, k_2 = jax.random.split(key, 3)
    dt = jnp.dtype(arch.dtype)
    w = jax.tree.map(lambda a: a.astype(dt), target_weights(k_t, arch))
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
    return draw_weights(seed_key(seed), arch, dcfg, _target_weights)


def block_tree(lay: Dict[str, Any], arch: Arch) -> Dict[str, Any]:
    """The program's parameters of one stacked block over the arrays of a
    :func:`layer_stack`."""
    attn = {"wq": lay["wq"], "wk": lay["wk"], "wv": lay["wv"],
            "wo": lay["wo"]}
    if arch.qkv_bias:
        attn.update(bq=lay["bq"], bk=lay["bk"], bv=lay["bv"])
    if arch.qk_norm:
        attn.update(q_norm=lay["q_norm"], k_norm=lay["k_norm"])
    return {"ln1": {"scale": lay["attn_norm"]}, "attn": attn,
            "ln2": {"scale": lay["mlp_norm"]},
            "ffn": {"w_in": lay["w_up"], "w_gate": lay["w_gate"],
                    "w_out": lay["w_down"]}}


def target_tree(w: Dict[str, Any], arch: Arch) -> Dict[str, Any]:
    """The program's target parameter tree over the same arrays: one
    period of one block, stacked over every layer."""
    p = {"tok": {"embedding": w["embed"]},
         "ln_f": {"scale": w["final_norm"]},
         "period": {"p0": block_tree(w["layers"], arch)}}
    if not arch.tied:
        p["lm_head"] = w["head"]
    return p


def bundle(arch: Arch, w, d1, d2):
    """The program's SpecBundle over the benchmark's weights."""
    from repro.core.pipeline import SpecBundle
    tcfg, dcfg, spec = program_configs(arch)
    return SpecBundle(tcfg, dcfg, dcfg, spec, target_tree(w, arch), d1, d2)
