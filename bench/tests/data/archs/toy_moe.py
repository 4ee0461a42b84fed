"""A small mixture-of-experts decoder, as an architecture module that the
harness loads from this directory: the tests' proof that a configuration
brings its own architecture as new files only (``archs/__init__.py``
states the contract).

Leading dense layers (``first_k_dense_replace``), then layers whose
feed-forward is ``n_routed_experts`` SwiGLU experts (every one held)
under a softmax router that keeps the ``num_experts_per_tok`` best and
renormalizes their gates, plus ``n_shared_experts`` always-on experts;
grouped-query attention with rotary positions and RMSNorm throughout.
The program serves it as ``family="moe"`` with no token dropped. The
reference below is plain ``jax.numpy`` at float32: every expert is
computed for every row and weighted by the gate (zero off the top k).
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Dict

import jax
import jax.numpy as jnp

from harness import reference
from harness.reference import HI, fp8_round, mm
from harness.weights import (EMBED_STD, layer_key, matrix, norm_scale,
                             normal, root_key)

BF16 = 2
F32 = 4
Q_BLOCK = 256
DENSE = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")
MOE = ("wq", "wk", "wv", "wo", "router", "e_gate", "e_up", "e_down",
       "s_gate", "s_up", "s_down")


@dataclass(frozen=True)
class Spec:
    """The toy model's dimensions."""
    name: str
    layers: int
    dense_layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    moe_d_ff: int
    experts: int
    shared: int
    top_k: int
    vocab: int
    norm_eps: float
    rope_theta: float
    tied: bool
    dtype: str


def spec(cfg: Dict[str, Any]) -> Spec:
    """Read the sizes from the configuration's Hugging Face keys."""
    return Spec(
        name=cfg["name"], layers=cfg["num_hidden_layers"],
        dense_layers=cfg["first_k_dense_replace"],
        d_model=cfg["hidden_size"], heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["intermediate_size"], moe_d_ff=cfg["moe_intermediate_size"],
        experts=cfg["n_routed_experts"], shared=cfg["n_shared_experts"],
        top_k=cfg["num_experts_per_tok"], vocab=cfg["vocab_size"],
        norm_eps=cfg["rms_norm_eps"], rope_theta=float(cfg["rope_theta"]),
        tied=bool(cfg["tie_word_embeddings"]), dtype=cfg["serve_dtype"])


def program_config(m: Spec):
    """The program's ModelConfig: family ``moe``."""
    from repro.configs.base import ModelConfig
    return ModelConfig(
        name=m.name, family="moe", num_layers=m.layers, d_model=m.d_model,
        num_heads=m.heads, num_kv_heads=m.kv_heads, head_dim=m.head_dim,
        d_ff=m.d_ff, vocab_size=m.vocab, num_experts=m.experts,
        num_shared_experts=m.shared, top_k=m.top_k, moe_d_ff=m.moe_d_ff,
        first_k_dense=m.dense_layers, norm_type="rmsnorm",
        activation="swiglu", rope_theta=m.rope_theta, tie_embeddings=m.tied,
        dtype=m.dtype)


# --- weights ------------------------------------------------------------ #
def _bank(key, n, d, f, dt):
    ks = jax.random.split(key, 3)
    return (normal(ks[0], (n, d, f), d ** -0.5, dt),
            normal(ks[1], (n, d, f), d ** -0.5, dt),
            normal(ks[2], (n, f, d), f ** -0.5, dt))


def layer_weights(key, m: Spec, moe: bool) -> Dict[str, jax.Array]:
    """One layer: attention, norm scales, and a SwiGLU feed-forward or
    (``moe``) the router, the routed and the shared experts."""
    d, H, K, hd = m.d_model, m.heads, m.kv_heads, m.head_dim
    dt = jnp.dtype(m.dtype)
    ks = jax.random.split(key, 9)
    w = {"wq": matrix(ks[0], d, H * hd, dt),
         "wk": matrix(ks[1], d, K * hd, dt),
         "wv": matrix(ks[2], d, K * hd, dt),
         "wo": matrix(ks[3], H * hd, d, dt),
         "attn_norm": norm_scale(ks[7], d),
         "ffn_norm": norm_scale(ks[8], d)}
    if moe:
        w["router"] = matrix(ks[4], d, m.experts, dt)
        w["e_gate"], w["e_up"], w["e_down"] = _bank(
            ks[5], m.experts, d, m.moe_d_ff, dt)
        w["s_gate"], w["s_up"], w["s_down"] = _bank(
            ks[6], m.shared, d, m.moe_d_ff, dt)
    else:
        w["w_gate"] = matrix(ks[4], d, m.d_ff, dt)
        w["w_up"] = matrix(ks[5], d, m.d_ff, dt)
        w["w_down"] = matrix(ks[6], m.d_ff, d, dt)
    return w


def outer_weights(key, m: Spec) -> Dict[str, jax.Array]:
    """Embedding, final norm scale and (untied) output head."""
    dt = jnp.dtype(m.dtype)
    ks = jax.random.split(key, 3)
    w = {"embed": normal(ks[0], (m.vocab, m.d_model), EMBED_STD, dt),
         "final_norm": norm_scale(ks[1], m.d_model)}
    if not m.tied:
        w["unembed"] = matrix(ks[2], m.d_model, m.vocab, dt)
    return w


def _program_layers(w, moe):
    out = {"ln1": {"scale": w["attn_norm"]},
           "attn": {k: w[k] for k in ("wq", "wk", "wv", "wo")},
           "ln2": {"scale": w["ffn_norm"]}}
    if moe:
        out["moe"] = {"router": w["router"],
                      "experts": {"wi": w["e_gate"], "wg": w["e_up"],
                                  "wo": w["e_down"]},
                      "shared": {"wi": w["s_gate"], "wg": w["s_up"],
                                 "wo": w["s_down"]}}
    else:
        out["ffn"] = {"wi": w["w_gate"], "wg": w["w_up"], "wo": w["w_down"]}
    return out


@functools.partial(jax.jit, static_argnames=("m",))
def program_params(key, m: Spec):
    """The server's parameter tree (``dense_layers``, ``moe_layers``)."""
    def stack(lo, hi, moe):
        return _program_layers(jax.vmap(
            lambda i: layer_weights(layer_key(key, i), m, moe))(
            jnp.arange(lo, hi)), moe)
    outer = outer_weights(layer_key(key, -1), m)
    params = {"embed": outer["embed"],
              "final_norm": {"scale": outer["final_norm"]},
              "moe_layers": stack(m.dense_layers, m.layers, True)}
    if m.dense_layers:
        params["dense_layers"] = stack(0, m.dense_layers, False)
    if not m.tied:
        params["unembed"] = outer["unembed"]
    return params


# --- reference ---------------------------------------------------------- #
def _norm(x, scale, m: Spec):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                             + m.norm_eps) * scale


def _rope(x, theta):
    S, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


@functools.partial(jax.jit, static_argnames=("m", "moe", "quant"))
def _layer_weights_f32(key, m: Spec, moe: bool, quant: bool):
    w = {k: v.astype(jnp.float32)
         for k, v in layer_weights(key, m, moe).items()}
    if quant:
        for k in MOE if moe else DENSE:
            w[k] = fp8_round(w[k], axis=w[k].ndim - 2)
    return w


def _swiglu(h, gate, up, down, r):
    return mm(r(jax.nn.silu(mm(h, gate)) * mm(h, up)), down)


@functools.partial(jax.jit, static_argnames=("m", "moe", "quant"),
                   donate_argnames=("x",))
def _layer(x, w, *, m: Spec, moe: bool, quant: bool):
    N, S, d = x.shape
    H, K, hd = m.heads, m.kv_heads, m.head_dim
    r = reference.rows(quant)
    h = r(_norm(x, w["attn_norm"], m))
    q = r(_rope(mm(h, w["wq"]).reshape(N, S, H, hd), m.rope_theta))
    k = r(_rope(mm(h, w["wk"]).reshape(N, S, K, hd), m.rope_theta))
    vt = r(jnp.swapaxes(mm(h, w["wv"]).reshape(N, S, K, hd), 1, 3))
    qg = q.reshape(N, S, K, H // K, hd)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(qg, i * Q_BLOCK, Q_BLOCK, axis=1)
        s = jnp.einsum("nqkgd,nskd->nkgqs", qb, k, precision=HI) * hd ** -0.5
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.where(jnp.arange(S)[None, :] <= qpos[:, None], s, -jnp.inf)
        p = r(jax.nn.softmax(s, axis=-1))
        o = jnp.einsum("nkgqs,ndks->nqkgd", p, vt, precision=HI)
        return o.reshape(N, Q_BLOCK, H * hd)

    o = jnp.moveaxis(jax.lax.map(block, jnp.arange(S // Q_BLOCK)), 0, 1)
    x = x + mm(r(o.reshape(N, S, H * hd)), w["wo"])
    h = r(_norm(x, w["ffn_norm"], m)).reshape(N * S, d)
    if not moe:
        return x + _swiglu(h, w["w_gate"], w["w_up"], w["w_down"],
                           r).reshape(N, S, d)
    probs = jax.nn.softmax(mm(h, w["router"]), -1)
    top, idx = jax.lax.top_k(probs, m.top_k)
    gates = jnp.zeros_like(probs).at[
        jnp.arange(N * S)[:, None], idx].set(top / top.sum(-1, keepdims=True))
    experts = jax.vmap(lambda g, u, dn: _swiglu(h, g, u, dn, r))
    y = jnp.einsum("re,erd->rd", gates,
                   experts(w["e_gate"], w["e_up"], w["e_down"]),
                   precision=HI)
    y = y + experts(w["s_gate"], w["s_up"], w["s_down"]).sum(0)
    return x + y.reshape(N, S, d)


@functools.partial(jax.jit, static_argnames=("m",))
def _embed(key, tokens, *, m: Spec):
    return outer_weights(key, m)["embed"].astype(jnp.float32)[tokens]


@functools.partial(jax.jit, static_argnames=("m", "quant"))
def _head(key, *, m: Spec, quant: bool):
    o = outer_weights(key, m)
    w = (o["embed"].T if m.tied else o["unembed"]).astype(jnp.float32)
    return o["final_norm"], (fp8_round(w) if quant else w)


@functools.partial(jax.jit, static_argnames=("m", "quant"))
def _logits(x_flat, idx, scale, head, *, m: Spec, quant: bool):
    return mm(reference.rows(quant)(_norm(x_flat[idx], scale, m)), head)


def gaps(m: Spec, seed: int, seqs, pad_to: int, *, control: bool = False):
    """Reference gaps of every served token of ``seqs``."""
    assert pad_to % Q_BLOCK == 0
    tokens, idx, served = reference.pack(seqs, pad_to)
    key = root_key(seed)
    okey = layer_key(key, -1)
    qs = reference.streams(control)
    xs = {q: _embed(okey, jnp.asarray(tokens), m=m) for q in qs}
    for layer in range(m.layers):
        moe = layer >= m.dense_layers
        for q in qs:
            w = _layer_weights_f32(layer_key(key, layer), m, moe, q)
            xs[q] = _layer(xs[q], w, m=m, moe=moe, quant=q)
    heads = {q: _head(okey, m=m, quant=q) for q in qs}
    flat = {q: xs[q].reshape(-1, m.d_model) for q in qs}
    return reference.head_gaps(
        lambda i, q: _logits(flat[q], i, *heads[q], m=m, quant=q),
        idx, served, control)


# --- counts ------------------------------------------------------------- #
def _attn_params(m: Spec) -> int:
    return 2 * m.d_model * m.head_dim * (m.heads + m.kv_heads)


def _active_ffn(m: Spec, moe: bool) -> int:
    if not moe:
        return 3 * m.d_model * m.d_ff
    return (m.d_model * m.experts
            + 3 * m.d_model * m.moe_d_ff * (m.top_k + m.shared))


def param_count(m: Spec) -> int:
    """All weights, every expert held."""
    n_moe = m.layers - m.dense_layers
    ffn = (m.dense_layers * 3 * m.d_model * m.d_ff
           + n_moe * (m.d_model * m.experts
                      + 3 * m.d_model * m.moe_d_ff * (m.experts + m.shared)))
    emb = m.vocab * m.d_model * (1 if m.tied else 2)
    return m.layers * _attn_params(m) + ffn + emb


def kv_bytes_per_token(m: Spec, itemsize: int = BF16) -> int:
    """K and V of one token over every layer."""
    return 2 * m.layers * m.kv_heads * m.head_dim * itemsize


def _layer_flops(m: Spec) -> float:
    n_moe = m.layers - m.dense_layers
    return 2.0 * (m.layers * _attn_params(m)
                  + m.dense_layers * _active_ffn(m, False)
                  + n_moe * _active_ffn(m, True))


def token_flops(m: Spec, context: int, logits: bool) -> float:
    """One token's operations: the router, its top-k and the shared
    experts, attention over ``context``; ``logits`` adds the head."""
    f = _layer_flops(m) + 4.0 * m.layers * context * m.heads * m.head_dim
    return f + (2.0 * m.d_model * m.vocab if logits else 0.0)


def prompt_flops(m: Spec, n_prompt: int) -> float:
    """A whole prompt prefilled: causal attention, one set of logits."""
    f = _layer_flops(m) * n_prompt
    f += 4.0 * m.layers * m.heads * m.head_dim * n_prompt * (n_prompt + 1) / 2
    return f + 2.0 * m.d_model * m.vocab


def decode_attn_work(m: Spec, context: int, spans: int = 1):
    """(operations, bytes) of one token's paged decode attention."""
    H, K, hd, L = m.heads, m.kv_heads, m.head_dim, m.layers
    nbytes = L * (2 * context * K * hd * BF16
                  + spans * (H * hd * BF16 + H * hd * F32 + 2 * H * F32))
    return 4.0 * L * context * H * hd, nbytes


def prefill_attn_work(m: Spec, n_query: int, prefix: int, spans: int = 1):
    """(operations, bytes) of one chunk's paged prefill attention."""
    H, K, hd, L = m.heads, m.kv_heads, m.head_dim, m.layers
    nbytes = L * (2 * prefix * K * hd * BF16
                  + spans * n_query * (H * hd * BF16 + H * hd * F32
                                       + 2 * H * F32))
    return 4.0 * L * n_query * prefix * H * hd, nbytes
