"""Shared model building blocks: norms, RoPE, activations, embeddings.

Parameters are plain pytrees (nested dicts of jnp arrays); every block is a
pair of functions ``init_*(key, cfg) -> params`` and a pure ``apply``.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig


# --------------------------------------------------------------------- #
# Norms
# --------------------------------------------------------------------- #
def init_norm(cfg: ModelConfig, dim: int):
    if cfg.norm_type == "rmsnorm":
        return {"scale": jnp.ones((dim,), jnp.float32)}
    if cfg.norm_type == "layernorm":
        return {"scale": jnp.ones((dim,), jnp.float32),
                "bias": jnp.zeros((dim,), jnp.float32)}
    if cfg.norm_type == "nonparametric_ln":
        return {}
    raise ValueError(cfg.norm_type)


def apply_norm(params, x, cfg: ModelConfig, eps: Optional[float] = None):
    eps = cfg.norm_eps if eps is None else eps
    xf = x.astype(jnp.float32)
    if cfg.norm_type == "rmsnorm":
        xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
        xf = xf * params["scale"]
    else:  # layernorm / nonparametric_ln
        mu = jnp.mean(xf, -1, keepdims=True)
        var = jnp.var(xf, -1, keepdims=True)
        xf = (xf - mu) * jax.lax.rsqrt(var + eps)
        if cfg.norm_type == "layernorm":
            xf = xf * params["scale"] + params["bias"]
    return xf.astype(x.dtype)


def rms_norm_headwise(x, scale, eps: float = 1e-6):
    """Per-head RMSNorm over the last (head_dim) axis — Qwen3/Chameleon qk-norm."""
    xf = x.astype(jnp.float32)
    xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (xf * scale).astype(x.dtype)


# --------------------------------------------------------------------- #
# Positional encodings
# --------------------------------------------------------------------- #
def rope_freqs(head_dim: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float):
    """x: [..., T, n_heads, head_dim]; positions: [..., T] (broadcastable)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                            # [hd/2]
    ang = positions[..., None].astype(jnp.float32) * freqs   # [..., T, hd/2]
    cos = jnp.cos(ang)[..., None, :]                         # [..., T, 1, hd/2]
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
    return out.astype(x.dtype)


def sinusoidal_embedding(positions: jax.Array, dim: int):
    """[..., T] -> [..., T, dim] classic transformer sinusoids."""
    half = dim // 2
    freq = jnp.exp(-math.log(10_000.0) * jnp.arange(half, dtype=jnp.float32)
                   / half)
    ang = positions[..., None].astype(jnp.float32) * freq
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# --------------------------------------------------------------------- #
# Linear / embedding initializers
# --------------------------------------------------------------------- #
def dense_init(key, in_dim: int, out_dim: int, dtype) -> jax.Array:
    std = in_dim ** -0.5
    return (jax.random.normal(key, (in_dim, out_dim), jnp.float32)
            * std).astype(dtype)


def embed_init(key, vocab: int, dim: int, dtype) -> jax.Array:
    return (jax.random.normal(key, (vocab, dim), jnp.float32) * 0.02
            ).astype(dtype)


# --------------------------------------------------------------------- #
# FFN (dense)
# --------------------------------------------------------------------- #
def init_ffn(key, cfg: ModelConfig, d_ff: Optional[int] = None):
    d, dff = cfg.d_model, d_ff or cfg.d_ff
    dtype = jnp.dtype(cfg.dtype)
    k1, k2, k3 = jax.random.split(key, 3)
    if cfg.activation in ("swiglu", "geglu"):
        return {"wi": dense_init(k1, d, dff, dtype),
                "wg": dense_init(k2, d, dff, dtype),
                "wo": dense_init(k3, dff, d, dtype)}
    return {"wi": dense_init(k1, d, dff, dtype),
            "wo": dense_init(k3, dff, d, dtype)}


def apply_ffn(params, x, cfg: ModelConfig):
    h = x @ params["wi"]
    if cfg.activation == "swiglu":
        h = jax.nn.silu(h) * (x @ params["wg"])
    elif cfg.activation == "geglu":
        h = jax.nn.gelu(h) * (x @ params["wg"])
    else:
        h = jax.nn.gelu(h)
    return h @ params["wo"]
