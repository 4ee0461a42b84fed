"""Attention layer: QKV projection, qk-norm, RoPE, backend-pluggable core.

The attention *core* (score/softmax/value) is injected so the same layer
definition serves training (causal flash), prefill (flash + KV export) and
decode (paged DistAttention with collective merge).
"""
from __future__ import annotations

from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.common import apply_rope, dense_init, rms_norm_headwise


def init_attention(key, cfg: ModelConfig):
    d, H, K, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    dtype = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], d, H * hd, dtype),
        "wk": dense_init(ks[1], d, K * hd, dtype),
        "wv": dense_init(ks[2], d, K * hd, dtype),
        "wo": dense_init(ks[3], H * hd, d, dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), jnp.float32)
        p["k_norm"] = jnp.ones((hd,), jnp.float32)
    return p


def qkv_project(params, x: jax.Array, positions: jax.Array, cfg: ModelConfig
                ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """x: [B, T, d] -> q [B,T,H,hd], k/v [B,T,K,hd] with qk-norm + RoPE."""
    B, T, _ = x.shape
    H, K, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = (x @ params["wq"]).reshape(B, T, H, hd)
    k = (x @ params["wk"]).reshape(B, T, K, hd)
    v = (x @ params["wv"]).reshape(B, T, K, hd)
    if cfg.qk_norm:
        q = rms_norm_headwise(q, params["q_norm"])
        k = rms_norm_headwise(k, params["k_norm"])
    if cfg.positional == "rope":
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


# Attention core signature: (q[B,T,H,hd], k[B,S,K,hd], v[B,S,K,hd]) -> [B,T,H,hd]
AttnCore = Callable[[jax.Array, jax.Array, jax.Array], jax.Array]


def apply_attention_train(
    params, x: jax.Array, positions: jax.Array, cfg: ModelConfig,
    core: AttnCore, *, window: int = 0,
) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """Full-sequence causal self-attention. Returns (out [B,T,d], (k, v))."""
    q, k, v = qkv_project(params, x, positions, cfg)
    out = core(q, k, v)
    B, T = x.shape[:2]
    out = out.reshape(B, T, -1).astype(x.dtype) @ params["wo"]
    return out, (k, v)


def make_causal_core(cfg: ModelConfig, *, backend: str = "xla",
                     window: int = 0, chunk: int = 512,
                     interpret: Optional[bool] = None,
                     acc_constraint=None) -> AttnCore:
    """Build the training/prefill attention core.

    backend "xla": chunked online-softmax in pure jnp (memory-bounded,
    scan over KV chunks — the lowering used for dry-runs).
    backend "pallas": the flash-prefill kernel; ``interpret`` None lets
    ``kernels.ops`` pick (interpret mode off-TPU, native on a TPU).
    backend "ref": naive full-matrix reference (tests/tiny shapes only).

    ``acc_constraint``: optional fn((o, m, l)) -> (o, m, l) applied to the
    online-softmax carry each chunk step. Without it GSPMD may reshard
    the accumulator every iteration of the KV-chunk scan — measured as 2
    full-activation all-reduces PER CHUNK per layer on small-d models
    (EXPERIMENTS.md §Perf-2).
    """
    scale = cfg.head_dim ** -0.5

    if backend == "pallas":
        from repro.kernels.ops import flash_prefill
        def core(q, k, v):
            return flash_prefill(q, k, v, scale=scale, window=window,
                                 interpret=interpret)
        return core

    if backend == "ref":
        from repro.core.attention import full_attention_prefill
        def core(q, k, v):
            return full_attention_prefill(q, k, v, scale=scale, window=window)
        return core

    from repro.core.online_softmax import (combine, empty_partial, finalize,
                                           micro_attention_prefill)

    def core(q, k, v):
        B, T, H, hd = q.shape
        S = k.shape[1]
        n_chunks = max(1, (S + chunk - 1) // chunk)
        pad = n_chunks * chunk - S
        if pad:
            k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
            v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kc = k.reshape(B, n_chunks, chunk, *k.shape[2:])
        vc = v.reshape(B, n_chunks, chunk, *v.shape[2:])
        q_pos = jnp.arange(T, dtype=jnp.int32)[None].repeat(B, 0)

        def body(acc, xs):
            kci, vci, idx = xs
            kv_pos = (idx * chunk + jnp.arange(chunk, dtype=jnp.int32)
                      )[None].repeat(B, 0)
            valid = kv_pos < S
            part = micro_attention_prefill(q, kci, vci, q_pos, kv_pos,
                                           valid, scale=scale, window=window)
            acc = combine(acc, part)
            if acc_constraint is not None:
                acc = acc_constraint(acc)
            return acc, None

        acc0 = empty_partial((B, T, H, hd), (B, T, H))
        xs = (jnp.moveaxis(kc, 1, 0), jnp.moveaxis(vc, 1, 0),
              jnp.arange(n_chunks, dtype=jnp.int32))
        acc, _ = jax.lax.scan(body, acc0, xs)
        return finalize(acc[0], acc[2]).astype(q.dtype)

    return core
