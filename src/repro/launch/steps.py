"""Step functions + abstract input specs for every (arch x shape) cell.

Used by the multi-pod dry-run, the roofline analysis, and the launchers.
``input_specs`` returns ShapeDtypeStruct stand-ins — weak-type-correct,
shardable, no device allocation. The full-size configs are only ever
exercised through these abstract paths.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config.base import ModelConfig, ShapeSpec, SpecConfig, \
    OptimizerConfig, shape_by_name
from repro.config.registry import get_config
from repro.core import pipeline as pl
from repro.core.drafter import DrafterConfig, drafter_init, init_feat_cache
from repro.models import api, encdec, lm
from repro.optim import optimizers as opt_lib

GAMMA_PROD = 16
K_PROD = 4


def production_drafter(tcfg: ModelConfig, gamma: int = GAMMA_PROD,
                       causal: bool = False) -> DrafterConfig:
    from repro.models.lm import feature_dim
    d = max(512, (tcfg.d_model // 4) // 128 * 128)
    heads = max(4, d // 128)
    kv = 2 if heads % 2 == 0 else 1          # must divide heads
    return DrafterConfig(
        d_model=d, num_layers=2, num_heads=heads,
        num_kv_heads=kv, d_ff=3 * d,
        vocab_size=tcfg.vocab_size, target_feature_dim=feature_dim(tcfg),
        gamma=gamma, causal=causal, param_dtype=tcfg.param_dtype)


def optimizer_for(cfg: ModelConfig) -> OptimizerConfig:
    # factored moments for the giant MoEs; int8 moments for mid-size; plain
    # AdamW for small models
    n = cfg.param_count()
    if n > 1e11:
        return OptimizerConfig(name="adafactor")
    if n > 3e9:
        return OptimizerConfig(name="adamw8bit")
    return OptimizerConfig(name="adamw")


def _cap_for(seq_len: int) -> int:
    return seq_len + 1024


# ---------------------------------------------------------------- specs ----
def abstract_params(cfg: ModelConfig, dtype=None):
    shapes = jax.eval_shape(lambda k: api.init_model(k, cfg),
                            jax.ShapeDtypeStruct((2,), jnp.uint32))
    if dtype is not None:
        shapes = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(
                s.shape, dtype if jnp.issubdtype(s.dtype, jnp.floating)
                else s.dtype), shapes)
    return shapes


def abstract_tree(fn, *args):
    return jax.eval_shape(fn, *args)


def _sds_tree(tree):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)


def make_bundle_abstract(cfg: ModelConfig, spec: SpecConfig,
                         serve_dtype=jnp.bfloat16):
    d1_cfg = production_drafter(cfg, spec.gamma)
    d2_cfg = production_drafter(cfg, spec.gamma)
    tp = abstract_params(cfg, serve_dtype)
    dp1 = jax.eval_shape(lambda k: drafter_init(k, d1_cfg),
                         jax.ShapeDtypeStruct((2,), jnp.uint32))
    dp2 = jax.eval_shape(lambda k: drafter_init(k, d2_cfg),
                         jax.ShapeDtypeStruct((2,), jnp.uint32))
    if serve_dtype is not None:
        cast = lambda s: jax.ShapeDtypeStruct(
            s.shape, serve_dtype if jnp.issubdtype(s.dtype, jnp.floating)
            else s.dtype)
        dp1 = jax.tree.map(cast, dp1)
        dp2 = jax.tree.map(cast, dp2)
    return pl.SpecBundle(cfg, d1_cfg, d2_cfg, spec, tp, dp1, dp2)


def ctx_len_for(cfg: ModelConfig) -> int:
    if cfg.is_encoder_decoder:
        return cfg.enc_max_len
    if cfg.cross_attn_every:
        return max(cfg.num_vision_tokens, 1)
    return 0


def engine_state_abstract(bundle, batch: int, max_len: int):
    return jax.eval_shape(
        lambda: pl.engine_init(bundle, batch, max_len,
                               ctx_len=ctx_len_for(bundle.target_cfg)))


# ----------------------------------------------------------- step makers ---
def make_train_step(cfg: ModelConfig, loss_seq_chunk: Optional[int] = None):
    hp = optimizer_for(cfg)
    opt_init, opt_update = opt_lib.make_optimizer(hp)

    def train_step(params, opt_state, batch):
        from repro.distributed.sharding import constrain_params
        params = constrain_params(params)
        loss, grads = jax.value_and_grad(
            lambda p: api.train_loss(p, batch, cfg,
                                     loss_seq_chunk=loss_seq_chunk))(params)
        new_p, new_o, metrics = opt_update(grads, opt_state, params)
        return new_p, new_o, {"loss": loss, **metrics}

    return train_step, opt_init


def make_prefill_step(cfg: ModelConfig):
    if cfg.is_encoder_decoder:
        def step(enc_params, bundle, est, prompts, audio_feats):
            ctx = encdec.encode(enc_params, audio_feats, cfg)
            return pl.prefill(bundle, est, prompts, ctx=ctx)
        return step
    if cfg.cross_attn_every:
        def step(bundle, est, prompts, image_embeds):
            return pl.prefill(bundle, est, prompts, ctx=image_embeds)
        return step

    def step(bundle, est, prompts):
        return pl.prefill(bundle, est, prompts)
    return step


def make_serve_step():
    def serve_step(bundle, est, key):
        return pl.decode_cycle(bundle, est, key, collect_stats=False)
    return serve_step


# ----------------------------------------------------------- cell specs ----
@dataclasses.dataclass
class CellSpec:
    """Everything needed to lower one (arch x shape) cell."""
    fn: Any                      # the step callable
    args: Tuple[Any, ...]        # abstract arguments (SDS pytrees)
    rules: Dict[str, Any]        # logical sharding rules profile
    fsdp: bool
    kind: str


def build_cell(arch: str, shape_name: str,
               gamma: int = GAMMA_PROD, k_branches: int = K_PROD,
               loss_seq_chunk: Optional[int] = None,
               remat_policy: Optional[str] = None) -> Optional[CellSpec]:
    """Returns None when the cell is skipped (long_500k on quadratic archs).
    """
    from repro.distributed.sharding import LOGICAL_RULES
    cfg = get_config(arch)
    if remat_policy is not None:
        cfg = dataclasses.replace(cfg, remat_policy=remat_policy)
    shape = shape_by_name(shape_name)
    if shape.name == "long_500k" and not cfg.is_subquadratic:
        return None

    rules = dict(LOGICAL_RULES)
    if shape.kind == "train":
        rules["act_seq"] = "model"
        rules["kv_seq"] = None
        spec_c = None
        step, opt_init = make_train_step(cfg, loss_seq_chunk)
        params = abstract_params(cfg)
        opt_state = jax.eval_shape(opt_init, params)
        batch = api.batch_specs(cfg, shape.global_batch, shape.seq_len)
        return CellSpec(step, (params, opt_state, batch), rules, True,
                        "train")

    spec_c = SpecConfig(gamma=gamma, top_k_branches=k_branches)
    bundle = make_bundle_abstract(cfg, spec_c)
    cap = _cap_for(shape.seq_len)
    # serving: TP-sharded weights replicated across data, except the giant
    # MoEs whose weights don't fit a single model-axis shard
    serve_fsdp = cfg.param_count() > 1e11

    if shape.kind == "prefill":
        rules["act_seq"] = "model"
        rules["kv_seq"] = "model"
        est = engine_state_abstract(bundle, shape.global_batch, cap)
        prompts = jax.ShapeDtypeStruct((shape.global_batch, shape.seq_len),
                                       jnp.int32)
        step = make_prefill_step(cfg)
        if cfg.is_encoder_decoder:
            enc = abstract_params(cfg, jnp.bfloat16)["encoder"]
            audio = jax.ShapeDtypeStruct(
                (shape.global_batch, cfg.enc_max_len, cfg.d_model),
                jnp.bfloat16)
            bundle_dec = dataclasses.replace(
                bundle, target_params=bundle.target_params["decoder"])
            args = (enc, bundle_dec, est, prompts, audio)
        elif cfg.cross_attn_every:
            img = jax.ShapeDtypeStruct(
                (shape.global_batch, max(cfg.num_vision_tokens, 1),
                 cfg.d_model), jnp.bfloat16)
            args = (bundle, est, prompts, img)
        else:
            args = (bundle, est, prompts)
        return CellSpec(step, args, rules, serve_fsdp, "prefill")

    # decode
    rules["act_seq"] = None
    rules["kv_seq"] = "model"
    est = engine_state_abstract(bundle, shape.global_batch, cap)
    if cfg.is_encoder_decoder:
        bundle = dataclasses.replace(
            bundle, target_params=bundle.target_params["decoder"])
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    return CellSpec(make_serve_step(), (bundle, est, key), rules,
                    serve_fsdp, "decode")
