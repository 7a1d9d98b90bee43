"""Basic layers: norms, MLPs, embeddings, RoPE.  Pure-functional (dict
params), no framework dependency; sharding is applied by annotation from
``repro.parallel.sharding``."""
from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

from .config import ModelConfig

__all__ = [
    "init_norm", "apply_norm", "init_mlp", "apply_mlp", "init_embedding",
    "embed", "unembed", "rope_freqs", "apply_rope", "init_dense", "dense",
    "truncated_normal",
]


def truncated_normal(key, shape, scale: float, dtype) -> jax.Array:
    """He/LeCun-style truncated-normal init (MaxText convention)."""
    stddev = scale / 0.87962566103423978
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
            * stddev).astype(dtype)


# -- norms ---------------------------------------------------------------------


def init_norm(cfg: ModelConfig, shape_d: int | None = None) -> dict:
    d = shape_d or cfg.d_model
    p = {"scale": jnp.ones((d,), jnp.dtype(cfg.param_dtype))}
    if cfg.norm == "layernorm":
        p["bias"] = jnp.zeros((d,), jnp.dtype(cfg.param_dtype))
    return p


def apply_norm(cfg: ModelConfig, p: dict, x: jax.Array, *,
               eps: float | None = None,
               use_kernel: bool = False) -> jax.Array:
    """RMSNorm / LayerNorm in fp32 accumulations, output in x.dtype;
    ``eps`` defaults to the configuration's ``norm_eps``."""
    eps = cfg.norm_eps if eps is None else eps
    if use_kernel and cfg.norm == "rmsnorm":
        from repro.kernels.rmsnorm import ops as rms_ops
        return rms_ops.rmsnorm(x, p["scale"].astype(jnp.float32), eps=eps)
    xf = x.astype(jnp.float32)
    if cfg.norm == "rmsnorm":
        var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        out = xf * jax.lax.rsqrt(var + eps) * p["scale"].astype(jnp.float32)
    else:
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        out = (xf - mu) * jax.lax.rsqrt(var + eps) \
            * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    return out.astype(x.dtype)


# -- dense / MLP -----------------------------------------------------------------


def init_dense(key, d_in: int, d_out: int, cfg: ModelConfig, *,
               bias: bool = False, scale: float | None = None) -> dict:
    scale = scale if scale is not None else 1.0 / math.sqrt(d_in)
    p = {"w": truncated_normal(key, (d_in, d_out), scale, jnp.dtype(cfg.param_dtype))}
    if bias:
        p["b"] = jnp.zeros((d_out,), jnp.dtype(cfg.param_dtype))
    return p


def dense(p: dict, x: jax.Array, compute_dtype) -> jax.Array:
    out = x.astype(compute_dtype) @ p["w"].astype(compute_dtype)
    if "b" in p:
        out = out + p["b"].astype(compute_dtype)
    return out


def _act(name: str, x: jax.Array) -> jax.Array:
    if name == "silu":
        return jax.nn.silu(x)
    if name == "gelu":
        return jax.nn.gelu(x)
    raise ValueError(f"unknown activation {name}")


def init_mlp(key, cfg: ModelConfig, d_ff: int | None = None, *,
             gated: bool | None = None) -> dict:
    """Gated (SwiGLU-style) or plain 2-layer MLP."""
    d, ff = cfg.d_model, d_ff or cfg.d_ff
    gated = gated if gated is not None else (cfg.act == "silu")
    ks = jax.random.split(key, 3)
    p = {"up": init_dense(ks[0], d, ff, cfg),
         "down": init_dense(ks[1], ff, d, cfg, scale=1.0 / math.sqrt(ff))}
    if gated:
        p["gate"] = init_dense(ks[2], d, ff, cfg)
    return p


def apply_mlp(cfg: ModelConfig, p: dict, x: jax.Array) -> jax.Array:
    cd = jnp.dtype(cfg.compute_dtype)
    up = dense(p["up"], x, cd)
    if "gate" in p:
        h = _act(cfg.act, dense(p["gate"], x, cd)) * up
    else:
        h = _act(cfg.act, up)
    return dense(p["down"], h, cd)


# -- embeddings -------------------------------------------------------------------


def init_embedding(key, cfg: ModelConfig) -> dict:
    V, d = cfg.padded_vocab, cfg.d_model
    ks = jax.random.split(key, 2)
    p = {"table": truncated_normal(ks[0], (V, d), 1.0, jnp.dtype(cfg.param_dtype))}
    if not cfg.tie_embeddings:
        p["unembed"] = truncated_normal(ks[1], (V, d), 1.0 / math.sqrt(d),
                                        jnp.dtype(cfg.param_dtype))
    return p


def embed(cfg: ModelConfig, p: dict, tokens: jax.Array) -> jax.Array:
    cd = jnp.dtype(cfg.compute_dtype)
    x = jnp.take(p["table"].astype(cd), tokens, axis=0)
    if cfg.embed_scale:
        x = x * jnp.asarray(math.sqrt(cfg.d_model), cd)
    if cfg.embed_multiplier != 1.0:
        x = x * jnp.asarray(cfg.embed_multiplier, cd)
    return x


def unembed(cfg: ModelConfig, p: dict, x: jax.Array) -> jax.Array:
    cd = jnp.dtype(cfg.compute_dtype)
    table = p.get("unembed", p["table"])
    logits = x.astype(cd) @ table.astype(cd).T
    if cfg.logits_divisor != 1.0:
        logits = logits / jnp.asarray(cfg.logits_divisor, cd)
    return logits


# -- RoPE -------------------------------------------------------------------------


def rope_freqs(cfg: ModelConfig, positions: jax.Array, hd: int | None = None,
               theta: float | None = None):
    """Returns (sin, cos) of shape positions.shape + (hd/2,), fp32."""
    hd = hd or cfg.hd
    theta = theta or cfg.rope_theta
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = positions.astype(jnp.float32)[..., None] * inv
    return jnp.sin(ang), jnp.cos(ang)


def apply_rope(x: jax.Array, sin: jax.Array, cos: jax.Array) -> jax.Array:
    """x: (..., seq, heads, hd); sin/cos: (..., seq, hd/2) broadcast over heads."""
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    s, c = sin[..., None, :], cos[..., None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s],
                           axis=-1).astype(x.dtype)
