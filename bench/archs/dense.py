"""The dense decoder: pre-norm layers of grouped-query attention with
rotary positions and a SwiGLU feed-forward (Mistral, Llama, OLMo).

Reads the Hugging Face keys ``num_hidden_layers``, ``hidden_size``,
``num_attention_heads``, ``num_key_value_heads``, ``head_dim`` (default
``hidden_size // num_attention_heads``), ``intermediate_size``,
``vocab_size``, ``rope_theta``, ``tie_word_embeddings`` and the
configuration's own ``norm`` (``rmsnorm`` with a scale, or
``layernorm`` with no parameters), ``rms_norm_eps`` / ``norm_eps`` and
``serve_dtype``.

The reference is the published decoder written out in ``jax.numpy`` at
float32 with the highest matmul precision: embedding; per layer a
pre-norm, multi-head attention with grouped KV heads and rotary
positions (half-split rotation), a SwiGLU feed-forward; final norm;
output head (tied: the embedding). No kernel, cache, batching or code of
the program. It runs once the window has closed and the server is
freed, one layer at a time over every sampled sequence, padded to a
fixed length so that its programs compile once per cell.
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
Q_BLOCK = 256        # query rows per attention block
ROW_BLOCK = 2048     # rows per feed-forward block
MATRICES = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


@dataclass(frozen=True)
class Spec:
    """The model dimensions the harness, the counts and the reference use."""
    name: str
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    norm: str            # "layernorm" (no scale or bias) | "rmsnorm"
    norm_eps: float
    rope_theta: float
    tied: bool
    dtype: str           # the type the weights are served in


def spec(cfg: Dict[str, Any]) -> Spec:
    """Read the sizes from a configuration file's Hugging Face keys."""
    heads = cfg["num_attention_heads"]
    return Spec(
        name=cfg["name"],
        layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"],
        heads=heads,
        kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg.get("head_dim") or cfg["hidden_size"] // heads,
        d_ff=cfg["intermediate_size"],
        vocab=cfg["vocab_size"],
        norm=cfg["norm"],
        norm_eps=cfg.get("rms_norm_eps", cfg.get("norm_eps", 1e-5)),
        rope_theta=float(cfg["rope_theta"]),
        tied=bool(cfg["tie_word_embeddings"]),
        dtype=cfg["serve_dtype"],
    )


def program_config(m: Spec):
    """The program's ModelConfig of the model."""
    from repro.configs.base import ModelConfig
    return ModelConfig(
        name=m.name, family="dense", num_layers=m.layers, d_model=m.d_model,
        num_heads=m.heads, num_kv_heads=m.kv_heads, head_dim=m.head_dim,
        d_ff=m.d_ff, vocab_size=m.vocab,
        norm_type="rmsnorm" if m.norm == "rmsnorm" else "nonparametric_ln",
        activation="swiglu", rope_theta=m.rope_theta, tie_embeddings=m.tied,
        dtype=m.dtype)


# --- weights ------------------------------------------------------------ #
def layer_weights(key: jax.Array, m: Spec) -> Dict[str, jax.Array]:
    """One transformer layer: attention, SwiGLU feed-forward, norm scales.

    Matrices have std ``fan_in ** -0.5`` and the served dtype; RMSNorm
    scales are float32 (a LayerNorm without parameters has none).
    """
    d, H, K, hd, F = m.d_model, m.heads, m.kv_heads, m.head_dim, m.d_ff
    dt = jnp.dtype(m.dtype)
    ks = jax.random.split(key, 9)
    w = {
        "wq": matrix(ks[0], d, H * hd, dt),
        "wk": matrix(ks[1], d, K * hd, dt),
        "wv": matrix(ks[2], d, K * hd, dt),
        "wo": matrix(ks[3], H * hd, d, dt),
        "w_gate": matrix(ks[4], d, F, dt),
        "w_up": matrix(ks[5], d, F, dt),
        "w_down": matrix(ks[6], F, d, dt),
    }
    if m.norm == "rmsnorm":
        w["attn_norm"] = norm_scale(ks[7], d)
        w["ffn_norm"] = norm_scale(ks[8], d)
    return w


def outer_weights(key: jax.Array, m: Spec) -> Dict[str, jax.Array]:
    """Embedding, final norm scale and (untied) output head."""
    dt = jnp.dtype(m.dtype)
    ks = jax.random.split(key, 3)
    w = {"embed": normal(ks[0], (m.vocab, m.d_model), EMBED_STD, dt)}
    if m.norm == "rmsnorm":
        w["final_norm"] = norm_scale(ks[1], m.d_model)
    if not m.tied:
        w["unembed"] = matrix(ks[2], m.d_model, m.vocab, dt)
    return w


def _norm_params(w, name):
    return {"scale": w[name]} if name in w else {}


@functools.partial(jax.jit, static_argnames=("m",))
def program_params(key: jax.Array, m: Spec):
    """The server's parameter tree (the program's layout), in one call."""
    layers = jax.vmap(lambda i: layer_weights(layer_key(key, i), m))(
        jnp.arange(m.layers))
    outer = outer_weights(layer_key(key, -1), m)
    params = {
        "embed": outer["embed"],
        "final_norm": _norm_params(outer, "final_norm"),
        "layers": {
            "ln1": _norm_params(layers, "attn_norm"),
            "attn": {k: layers[k] for k in ("wq", "wk", "wv", "wo")},
            "ln2": _norm_params(layers, "ffn_norm"),
            "ffn": {"wi": layers["w_gate"], "wg": layers["w_up"],
                    "wo": layers["w_down"]},
        },
    }
    if not m.tied:
        params["unembed"] = outer["unembed"]
    return params


# --- reference ---------------------------------------------------------- #
def _norm(x, scale, m: Spec):
    if m.norm == "rmsnorm":
        x = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                              + m.norm_eps)
        return x * scale
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + m.norm_eps)


def _rope(x, theta):
    """x [N, S, h, hd] at positions 0..S-1; rotate the two halves."""
    S, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


@functools.partial(jax.jit, static_argnames=("m", "quant"))
def _layer_weights_f32(key, m: Spec, quant: bool):
    w = {k: v.astype(jnp.float32) for k, v in layer_weights(key, m).items()}
    if quant:
        for k in MATRICES:
            w[k] = fp8_round(w[k])
    return w


@functools.partial(jax.jit, static_argnames=("m", "quant"),
                   donate_argnames=("x",))
def _layer(x, w, *, m: Spec, quant: bool):
    """One decoder layer over x [N, S, d] (causal, positions 0..S-1);
    ``quant`` rounds every matmul's activation operand to float8 rows."""
    N, S, d = x.shape
    H, K, hd = m.heads, m.kv_heads, m.head_dim
    G = H // K
    r = reference.rows(quant)
    h = r(_norm(x, w.get("attn_norm"), m))
    q = r(_rope(mm(h, w["wq"]).reshape(N, S, H, hd), m.rope_theta))
    k = r(_rope(mm(h, w["wk"]).reshape(N, S, K, hd), m.rope_theta))
    v = mm(h, w["wv"]).reshape(N, S, K, hd)
    vt = r(jnp.swapaxes(v, 1, 3))                        # rows over keys
    qg = q.reshape(N, S, K, G, hd)
    kpos = jnp.arange(S)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(qg, i * Q_BLOCK, Q_BLOCK, axis=1)
        s = jnp.einsum("nqkgd,nskd->nkgqs", qb, k, precision=HI)
        s = s * hd ** -0.5
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.where(kpos[None, :] <= qpos[:, None], s, -jnp.inf)
        p = r(jax.nn.softmax(s, axis=-1))
        o = jnp.einsum("nkgqs,ndks->nqkgd", p, vt, precision=HI)
        return o.reshape(N, Q_BLOCK, H * hd)

    o = jax.lax.map(block, jnp.arange(S // Q_BLOCK))     # [B, N, Qb, H*hd]
    o = jnp.moveaxis(o, 0, 1).reshape(N, S, H * hd)
    x = x + mm(r(o), w["wo"])
    h = r(_norm(x, w.get("ffn_norm"), m)).reshape(-1, ROW_BLOCK, d)

    def ffn(hb):
        g = mm(hb, w["w_gate"])
        return mm(r(jax.nn.silu(g) * mm(hb, w["w_up"])), w["w_down"])

    return x + jax.lax.map(ffn, h).reshape(N, S, d)


@functools.partial(jax.jit, static_argnames=("m",))
def _embed(key, tokens, *, m: Spec):
    return outer_weights(key, m)["embed"].astype(jnp.float32)[tokens]


@functools.partial(jax.jit, static_argnames=("m", "quant"))
def _head(key, *, m: Spec, quant: bool):
    """(final norm scale or None, output head [d, V]) in float32."""
    o = outer_weights(key, m)
    w = (o["embed"].T if m.tied else o["unembed"]).astype(jnp.float32)
    return o.get("final_norm"), (fp8_round(w) if quant else w)


@functools.partial(jax.jit, static_argnames=("m", "quant"))
def _logits(x_flat, idx, scale, head, *, m: Spec, quant: bool):
    return mm(reference.rows(quant)(_norm(x_flat[idx], scale, m)), head)


def gaps(m: Spec, seed: int, seqs: reference.Seqs, pad_to: int, *,
         control: bool = False) -> Dict[str, Any]:
    """Reference gaps of every served token of ``seqs`` (see
    ``harness.reference``)."""
    assert pad_to % ROW_BLOCK == 0 and pad_to % Q_BLOCK == 0
    tokens, idx, served = reference.pack(seqs, pad_to)
    key = root_key(seed)
    okey = layer_key(key, -1)
    qs = reference.streams(control)
    xs = {q: _embed(okey, jnp.asarray(tokens), m=m) for q in qs}
    for layer in range(m.layers):
        lk = layer_key(key, layer)
        for q in qs:
            w = _layer_weights_f32(lk, m, q)
            xs[q] = _layer(xs[q], w, m=m, quant=q)
            del w
    heads = {q: _head(okey, m=m, quant=q) for q in qs}
    flat = {q: xs[q].reshape(-1, m.d_model) for q in qs}
    return reference.head_gaps(
        lambda i, q: _logits(flat[q], i, *heads[q], m=m, quant=q),
        idx, served, control)


# --- counts ------------------------------------------------------------- #
def layer_params(m: Spec) -> int:
    """Weights of one transformer layer (matrices only)."""
    d, H, K, hd, F = m.d_model, m.heads, m.kv_heads, m.head_dim, m.d_ff
    return d * H * hd * 2 + d * K * hd * 2 + 3 * d * F


def param_count(m: Spec) -> int:
    """All weights: layers, embedding and (untied) head."""
    emb = m.vocab * m.d_model
    return m.layers * layer_params(m) + emb * (1 if m.tied else 2)


def kv_bytes_per_token(m: Spec, itemsize: int = BF16) -> int:
    """K and V of one token over every layer."""
    return 2 * m.layers * m.kv_heads * m.head_dim * itemsize


def token_flops(m: Spec, context: int, logits: bool) -> float:
    """Forward operations of one token that attends ``context`` tokens
    (itself included); ``logits`` adds the output head."""
    f = 2.0 * m.layers * layer_params(m)
    f += 4.0 * m.layers * context * m.heads * m.head_dim
    if logits:
        f += 2.0 * m.d_model * m.vocab
    return f


def prompt_flops(m: Spec, n_prompt: int) -> float:
    """A whole prompt prefilled: causal attention, one set of logits."""
    f = 2.0 * m.layers * layer_params(m) * n_prompt
    f += 4.0 * m.layers * m.heads * m.head_dim * n_prompt * (n_prompt + 1) / 2
    return f + 2.0 * m.d_model * m.vocab


def decode_attn_work(m: Spec, context: int, spans: int = 1):
    """(operations, bytes) of the paged decode attention of one token
    over ``context`` tokens held in ``spans`` pools, all layers: read K
    and V of the context once, q in, (o, m, l) out per span."""
    H, K, hd, L = m.heads, m.kv_heads, m.head_dim, m.layers
    flops = 4.0 * L * context * H * hd
    nbytes = L * (2 * context * K * hd * BF16
                  + spans * (H * hd * BF16 + H * hd * F32 + 2 * H * F32))
    return flops, nbytes


def prefill_attn_work(m: Spec, n_query: int, prefix: int, spans: int = 1):
    """(operations, bytes) of the paged prefill attention of one chunk:
    ``n_query`` chunk tokens over ``prefix`` already written tokens in
    ``spans`` pools, all layers. The chunk's causal part is not the
    kernel's work and is not counted."""
    H, K, hd, L = m.heads, m.kv_heads, m.head_dim, m.layers
    flops = 4.0 * L * n_query * prefix * H * hd
    nbytes = L * (2 * prefix * K * hd * BF16
                  + spans * n_query * (H * hd * BF16 + H * hd * F32
                                       + 2 * H * F32))
    return flops, nbytes
