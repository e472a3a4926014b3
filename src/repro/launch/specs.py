"""ShapeDtypeStruct stand-ins for every model input — weak-type-correct,
shardable, zero allocation. ``input_specs(cfg, shape)`` is the single
source of truth the launch drivers share.

long_500k eligibility: sub-quadratic archs only (DESIGN.md §4); callers
should consult ``shape_supported`` before lowering.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, ShapeConfig
from repro.models import frontend as F
from repro.models import model as M

PARAM_DTYPE = jnp.bfloat16


def shape_supported(cfg: ArchConfig, shape: ShapeConfig):
    """(ok, reason) — which (arch × shape) pairs run."""
    if shape.name == "long_500k" and not cfg.subquadratic:
        return False, ("full-attention architecture without a sliding-window/"
                       "block-sparse variant; long_500k skipped per task rules")
    return True, ""


def params_spec(cfg: ArchConfig, dtype=PARAM_DTYPE):
    return jax.eval_shape(
        lambda k: M.init_lm(cfg, k, dtype), jax.random.PRNGKey(0))


def cache_spec_tree(cfg: ArchConfig, batch, cache_len, dtype=PARAM_DTYPE):
    enc_len = F.AUDIO_FRAMES if cfg.layout == "encdec" else 0
    return jax.eval_shape(
        lambda: M.init_cache(cfg, batch, cache_len, enc_len, dtype))


def input_specs(cfg: ArchConfig, shape: ShapeConfig, dtype=PARAM_DTYPE):
    """Step inputs (excluding params/opt state) for (arch × shape)."""
    B, S = shape.global_batch, shape.seq_len
    i32 = jnp.int32
    if shape.mode == "train":
        specs = {"tokens": jax.ShapeDtypeStruct((B, S), i32),
                 "labels": jax.ShapeDtypeStruct((B, S), i32)}
        if cfg.layout == "encdec":
            specs["frames"] = jax.ShapeDtypeStruct(
                (B, F.AUDIO_FRAMES, cfg.d_model), dtype)
        return specs
    if shape.mode == "prefill":
        specs = {"tokens": jax.ShapeDtypeStruct((B, S), i32)}
        if cfg.layout == "encdec":
            specs["frames"] = jax.ShapeDtypeStruct(
                (B, F.AUDIO_FRAMES, cfg.d_model), dtype)
        return specs
    # decode: one new token against a seq_len-deep cache
    return {"token": jax.ShapeDtypeStruct((B, 1), i32),
            "pos": jax.ShapeDtypeStruct((), i32),
            "cache": cache_spec_tree(cfg, B, S, dtype)}
