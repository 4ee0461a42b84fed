"""Latent attention with sparse experts: DeepSeek-V2 (and -Lite).

Reads the Hugging Face keys ``num_hidden_layers``,
``first_k_dense_replace``, ``hidden_size``, ``num_attention_heads``,
``q_lora_rank``, ``kv_lora_rank``, ``qk_nope_head_dim``,
``qk_rope_head_dim``, ``v_head_dim``, ``intermediate_size``,
``moe_intermediate_size``, ``n_routed_experts`` (the experts held here;
the router's width is ``published["n_routed_experts"]`` when the count
is cut), ``n_shared_experts``, ``num_experts_per_tok``,
``norm_topk_prob``, ``routed_scaling_factor``, ``vocab_size``,
``rms_norm_eps``, ``rope_theta``, ``rope_scaling`` (YaRN),
``tie_word_embeddings`` and the configuration's ``serve_dtype``. Held
experts are the first ``n_routed_experts`` of the router's outputs.

The reference is the published model written out in ``jax.numpy`` at
float32 with the highest matmul precision, in its expanded form: per
layer an RMS pre-norm; the query (full rank); ``wkv_a`` to the
compressed KV and the shared rotary key; the compressed KV's RMSNorm and
``wkv_b`` to every head's no-rope key and value; YaRN rotary positions
on the 64 rope dims with DeepSeek's pairing (pairs de-interleaved into
halves, then rotated half against half); causal softmax attention at
``(qk_nope + qk_rope) ** -0.5 * mscale ** 2``, the output projection;
then a SwiGLU feed-forward (the leading dense layers) or the expert
layer: a softmax router over all experts keeps the best
``num_experts_per_tok`` with their raw probabilities as gates (no
renormalisation, scale 1), the held experts' SwiGLU outputs weighted by
their gates, plus the shared experts; final RMSNorm, output head. It
imports nothing of the program and does not absorb ``W_UK`` or cache.
It runs once the window has closed, one layer at a time over every
sampled sequence padded to a fixed length, attending in blocks of
``BLOCK`` queries over the key blocks at or before them (online
softmax), so that it fits at ~123k tokens.

Counts are those of the program's absorbed decode: a head scores a
token's cached row (``kv_lora_rank + qk_rope_head_dim`` wide) and sums
its first ``kv_lora_rank`` entries, so attention over ``ctx`` tokens is
``2 * heads * ctx * (2 * kv_lora_rank + qk_rope_head_dim)`` operations
a layer and reads ``ctx`` rows; expert work counts the top-k routes
that land on held experts (``top_k * held / router`` per token).
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from harness import reference
from harness.reference import HI, fp8_round, mm
from harness.weights import (EMBED_STD, layer_key, matrix, norm_scale,
                             normal, root_key)

BF16 = 2
F32 = 4
BLOCK = 1024         # query and key rows per attention block
ROW_BLOCK = 2048     # rows per feed-forward block
ATTN = ("wq", "wkv_a", "wkv_b", "wo")
DENSE = ATTN + ("w_gate", "w_up", "w_down")
MOE = ATTN + ("router", "e_gate", "e_up", "e_down", "s_gate", "s_up",
              "s_down")


@dataclass(frozen=True)
class Spec:
    """The model dimensions the harness, the counts and the reference use."""
    name: str
    layers: int
    dense_layers: int
    d_model: int
    heads: int
    kv_rank: int
    nope: int
    rope: int
    v_dim: int
    d_ff: int
    moe_d_ff: int
    experts: int          # routed experts held here
    router: int           # the router's outputs (all routed experts)
    shared: int
    top_k: int
    norm_topk: bool
    vocab: int
    norm_eps: float
    rope_theta: float
    rope_factor: float
    rope_orig: int
    beta_fast: float
    beta_slow: float
    mscale: float
    mscale_all: float
    tied: bool
    dtype: str


def spec(cfg: Dict[str, Any]) -> Spec:
    """Read the sizes from a configuration file's Hugging Face keys."""
    if cfg.get("q_lora_rank"):
        raise ValueError("a query LoRA is not in this reference")
    if cfg.get("routed_scaling_factor", 1) != 1:
        raise ValueError("routed_scaling_factor other than 1")
    held = cfg["n_routed_experts"]
    router = cfg.get("published", {}).get("n_routed_experts", held)
    y = cfg.get("rope_scaling") or {}
    return Spec(
        name=cfg["name"], layers=cfg["num_hidden_layers"],
        dense_layers=cfg["first_k_dense_replace"],
        d_model=cfg["hidden_size"], heads=cfg["num_attention_heads"],
        kv_rank=cfg["kv_lora_rank"], nope=cfg["qk_nope_head_dim"],
        rope=cfg["qk_rope_head_dim"], v_dim=cfg["v_head_dim"],
        d_ff=cfg["intermediate_size"],
        moe_d_ff=cfg["moe_intermediate_size"], experts=held, router=router,
        shared=cfg["n_shared_experts"], top_k=cfg["num_experts_per_tok"],
        norm_topk=bool(cfg["norm_topk_prob"]), vocab=cfg["vocab_size"],
        norm_eps=cfg["rms_norm_eps"], rope_theta=float(cfg["rope_theta"]),
        rope_factor=float(y.get("factor", 0.0)),
        rope_orig=int(y.get("original_max_position_embeddings", 0)),
        beta_fast=float(y.get("beta_fast", 32)),
        beta_slow=float(y.get("beta_slow", 1)),
        mscale=float(y.get("mscale", 1.0)),
        mscale_all=float(y.get("mscale_all_dim", 0.0)),
        tied=bool(cfg["tie_word_embeddings"]), dtype=cfg["serve_dtype"])


def program_config(m: Spec):
    """The program's ModelConfig: family ``mla``, the first ``experts``
    of the router's outputs held."""
    from repro.configs.base import ModelConfig
    return ModelConfig(
        name=m.name, family="mla", num_layers=m.layers, d_model=m.d_model,
        num_heads=m.heads, num_kv_heads=m.heads, d_ff=m.d_ff,
        vocab_size=m.vocab, num_experts=m.router,
        num_shared_experts=m.shared, top_k=m.top_k, moe_d_ff=m.moe_d_ff,
        first_k_dense=m.dense_layers, norm_topk_prob=m.norm_topk,
        held_experts=(0, m.experts), kv_lora_rank=m.kv_rank,
        qk_nope_head_dim=m.nope, qk_rope_head_dim=m.rope,
        v_head_dim=m.v_dim, rope_theta=m.rope_theta,
        rope_factor=m.rope_factor, rope_original_max_pos=m.rope_orig,
        rope_beta_fast=m.beta_fast, rope_beta_slow=m.beta_slow,
        rope_mscale=m.mscale, rope_mscale_all_dim=m.mscale_all,
        norm_eps=m.norm_eps, norm_type="rmsnorm", activation="swiglu",
        tie_embeddings=m.tied, dtype=m.dtype)


# --- weights ------------------------------------------------------------ #
def _bank(key, n, d, f, dt):
    ks = jax.random.split(key, 3)
    return (normal(ks[0], (n, d, f), d ** -0.5, dt),
            normal(ks[1], (n, d, f), d ** -0.5, dt),
            normal(ks[2], (n, f, d), f ** -0.5, dt))


def layer_weights(key, m: Spec, moe: bool) -> Dict[str, jax.Array]:
    """One layer: latent attention, norm scales, and a SwiGLU
    feed-forward or (``moe``) the router, the held and the shared
    experts. Matrices have std ``fan_in ** -0.5``."""
    d, H = m.d_model, m.heads
    dt = jnp.dtype(m.dtype)
    ks = jax.random.split(key, 11)
    w = {"wq": matrix(ks[0], d, H * (m.nope + m.rope), dt),
         "wkv_a": matrix(ks[1], d, m.kv_rank + m.rope, dt),
         "kv_norm": norm_scale(ks[2], m.kv_rank),
         "wkv_b": matrix(ks[3], m.kv_rank, H * (m.nope + m.v_dim), dt),
         "wo": matrix(ks[4], H * m.v_dim, d, dt),
         "attn_norm": norm_scale(ks[5], d),
         "ffn_norm": norm_scale(ks[6], d)}
    if moe:
        w["router"] = matrix(ks[7], d, m.router, dt)
        w["e_gate"], w["e_up"], w["e_down"] = _bank(
            ks[8], m.experts, d, m.moe_d_ff, dt)
        w["s_gate"], w["s_up"], w["s_down"] = _bank(
            ks[9], m.shared, d, m.moe_d_ff, dt)
    else:
        w["w_gate"] = matrix(ks[7], d, m.d_ff, dt)
        w["w_up"] = matrix(ks[8], d, m.d_ff, dt)
        w["w_down"] = matrix(ks[9], m.d_ff, d, dt)
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
           "attn": {"wq": w["wq"], "wkv_a": w["wkv_a"],
                    "kv_norm": {"scale": w["kv_norm"]},
                    "wkv_b": w["wkv_b"], "wo": w["wo"]},
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
    """The server's parameter tree (``dense_layers``, ``moe_layers``),
    in one call."""
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
def _rms(x, scale, m: Spec):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                             + m.norm_eps) * scale


def _yarn_mscale(factor, mscale):
    return 0.1 * mscale * np.log(factor) + 1.0 if factor > 1 else 1.0


def _rope_tables(m: Spec, S: int):
    """cos, sin [S, rope] of DeepSeek-V2's YaRN rotary embedding, made on
    the host: float32 angles, as the published model makes them, and
    their cosines and sines taken in float64. The layer program takes
    them as arguments; as constants they would make it ~270 MB at
    S ~123k."""
    dim, base = m.rope, m.rope_theta
    extra = 1.0 / base ** (np.arange(0, dim, 2) / dim)
    inv = extra
    if m.rope_factor > 1:
        def cdim(rot):
            return (dim * np.log(m.rope_orig / (rot * 2 * np.pi))
                    / (2 * np.log(base)))
        lo = max(int(np.floor(cdim(m.beta_fast))), 0)
        hi = min(int(np.ceil(cdim(m.beta_slow))), dim - 1)
        hi = hi + 0.001 if lo == hi else hi
        ramp = np.clip((np.arange(dim // 2) - lo) / (hi - lo), 0.0, 1.0)
        inv = (extra / m.rope_factor) * ramp + extra * (1.0 - ramp)
    ang = (np.arange(S)[:, None] * inv.astype(np.float32)[None]).astype(
        np.float32)
    emb = np.concatenate([ang, ang], -1).astype(np.float64)
    scale = (_yarn_mscale(m.rope_factor, m.mscale)
             / _yarn_mscale(m.rope_factor, m.mscale_all))
    return (jnp.asarray(np.cos(emb) * scale, jnp.float32),
            jnp.asarray(np.sin(emb) * scale, jnp.float32))


def _rotate(x, cos, sin):
    """x [S, h, rope]: pairs de-interleaved, then rotated half against
    half (DeepSeek-V2's apply_rotary_pos_emb)."""
    S, h, d = x.shape
    x = jnp.swapaxes(x.reshape(S, h, d // 2, 2), -1, -2).reshape(S, h, d)
    rot = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos[:, None] + rot * sin[:, None]


def _attend(qn, qp, kn, kp, vt, scale, r):
    """Causal attention of one sequence: scores ``qn . kn + qp . kp``
    (qn [S, H, nope], qp [S, H, rope], kn [S, H, nope], kp [S, rope]),
    values vt [H, v, S] (rows over keys); BLOCK queries over the key
    blocks at or before them, softmax online; -> [S, H, v]."""
    S, H, _ = qn.shape
    tri = jnp.arange(BLOCK)[None, :] <= jnp.arange(BLOCK)[:, None]

    def sl(a, i, axis=0):
        return jax.lax.dynamic_slice_in_dim(a, i * BLOCK, BLOCK, axis=axis)

    def qblock(i):
        qnb, qpb = sl(qn, i), sl(qp, i)

        def kblock(j, carry):
            o, mx, l = carry
            s = (jnp.einsum("qhd,khd->hqk", qnb, sl(kn, j), precision=HI)
                 + jnp.einsum("qhd,kd->hqk", qpb, sl(kp, j), precision=HI))
            s = jnp.where((j < i) | tri, s * scale, -jnp.inf)
            m_new = jnp.maximum(mx, jnp.max(s, -1))
            alpha = jnp.exp(mx - m_new)
            p = jnp.exp(s - m_new[..., None])
            pv = jnp.einsum("hqk,hdk->hqd", r(p), sl(vt, j, 2),
                            precision=HI)
            return o * alpha[..., None] + pv, m_new, l * alpha + p.sum(-1)

        o, _, l = jax.lax.fori_loop(
            0, i + 1, kblock,
            (jnp.zeros((H, BLOCK, vt.shape[1]), jnp.float32),
             jnp.full((H, BLOCK), -jnp.inf, jnp.float32),
             jnp.zeros((H, BLOCK), jnp.float32)))
        return jnp.swapaxes(o / l[..., None], 0, 1)       # [BLOCK, H, v]

    return jax.lax.map(qblock, jnp.arange(S // BLOCK)).reshape(S, H, -1)


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


def _sequence_layer(x, w, cos, sin, m: Spec, moe: bool, quant: bool):
    """One decoder layer over one sequence x [S, d]; cos, sin [S, rope]
    from ``_rope_tables``."""
    S, d = x.shape
    H, n, kr = m.heads, m.nope, m.kv_rank
    r = reference.rows(quant)
    h = r(_rms(x, w["attn_norm"], m))
    q = mm(h, w["wq"]).reshape(S, H, -1)
    kv = mm(h, w["wkv_a"])
    kp = r(_rotate(kv[:, None, kr:], cos, sin)[:, 0])
    kvb = mm(r(_rms(kv[:, :kr], w["kv_norm"], m)), w["wkv_b"]).reshape(
        S, H, -1)
    qn, qp = r(q[..., :n]), r(_rotate(q[..., n:], cos, sin))
    kn = r(kvb[..., :n])
    vt = r(jnp.transpose(kvb[..., n:], (1, 2, 0)))        # rows over keys
    del q, kv, kvb
    scale = (n + m.rope) ** -0.5
    if m.rope_factor > 1 and m.mscale_all:
        scale *= _yarn_mscale(m.rope_factor, m.mscale_all) ** 2
    o = _attend(qn, qp, kn, kp, vt, scale, r)
    x = x + mm(r(o.reshape(S, H * m.v_dim)), w["wo"])
    h = r(_rms(x, w["ffn_norm"], m)).reshape(-1, ROW_BLOCK, d)

    def ffn(hb):
        if not moe:
            return _swiglu(hb, w["w_gate"], w["w_up"], w["w_down"], r)
        probs = jax.nn.softmax(mm(hb, w["router"]), -1)
        top, idx = jax.lax.top_k(probs, m.top_k)
        if m.norm_topk:
            top = top / top.sum(-1, keepdims=True)
        gates = jnp.zeros_like(probs).at[
            jnp.arange(hb.shape[0])[:, None], idx].set(top)
        experts = jax.vmap(lambda g, u, dn: _swiglu(hb, g, u, dn, r))
        y = jnp.einsum("re,erd->rd", gates[:, :m.experts],
                       experts(w["e_gate"], w["e_up"], w["e_down"]),
                       precision=HI)
        return y + experts(w["s_gate"], w["s_up"], w["s_down"]).sum(0)

    return x + jax.lax.map(ffn, h).reshape(S, d)


@functools.partial(jax.jit, static_argnames=("m", "moe", "quant"),
                   donate_argnames=("x",))
def _layer(x, w, cos, sin, *, m: Spec, moe: bool, quant: bool):
    """One decoder layer over one sequence x [S, d] (causal, positions
    0..S-1); ``quant`` rounds every matmul's operands to float8 rows."""
    return _sequence_layer(x, w, cos, sin, m, moe, quant)


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
    return mm(reference.rows(quant)(_rms(x_flat[idx], scale, m)), head)


def gaps(m: Spec, seed: int, seqs, pad_to: int, *, control: bool = False):
    """Reference gaps of every served token of ``seqs`` (see
    ``harness.reference``)."""
    assert pad_to % BLOCK == 0 and pad_to % ROW_BLOCK == 0
    with jax.default_matmul_precision("highest"):
        return _gaps(m, seed, seqs, pad_to, control)


def _gaps(m: Spec, seed: int, seqs, pad_to: int, control: bool):
    tokens, idx, served = reference.pack(seqs, pad_to)
    key = root_key(seed)
    okey = layer_key(key, -1)
    qs = reference.streams(control)
    cos, sin = _rope_tables(m, pad_to)
    # One sequence per layer call: at ~123k tokens a layer's float32
    # temporaries are ~6 GB a sequence.
    xs = {q: [_embed(okey, jnp.asarray(t), m=m) for t in tokens]
          for q in qs}
    for layer in range(m.layers):
        moe = layer >= m.dense_layers
        for q in qs:
            w = _layer_weights_f32(layer_key(key, layer), m, moe, q)
            xs[q] = [_layer(x, w, cos, sin, m=m, moe=moe, quant=q)
                     for x in xs[q]]
            del w
    heads = {q: _head(okey, m=m, quant=q) for q in qs}
    flat = {q: jnp.concatenate(xs.pop(q)) for q in qs}
    return reference.head_gaps(
        lambda i, q: _logits(flat[q], i, *heads[q], m=m, quant=q),
        idx, served, control)


# --- counts ------------------------------------------------------------- #
def attn_params(m: Spec) -> int:
    """Matrices of one latent attention layer."""
    d, H = m.d_model, m.heads
    return (d * H * (m.nope + m.rope) + d * (m.kv_rank + m.rope)
            + m.kv_rank * H * (m.nope + m.v_dim) + H * m.v_dim * d)


def _expert(m: Spec) -> int:
    return 3 * m.d_model * m.moe_d_ff


def param_count(m: Spec) -> int:
    """All weights held here: layers (held experts only), embedding and
    (untied) head."""
    n_moe = m.layers - m.dense_layers
    ffn = (m.dense_layers * 3 * m.d_model * m.d_ff
           + n_moe * (m.d_model * m.router
                      + _expert(m) * (m.experts + m.shared)))
    emb = m.vocab * m.d_model * (1 if m.tied else 2)
    return m.layers * attn_params(m) + ffn + emb


def row_width(m: Spec) -> int:
    """Entries of one cached latent row: ``[c_kv | k_pe]``."""
    return m.kv_rank + m.rope


def kv_bytes_per_token(m: Spec, itemsize: int = BF16) -> int:
    """The latent row of one token over every layer."""
    return m.layers * row_width(m) * itemsize


def _token_weight_flops(m: Spec) -> float:
    """Operations of one token's matrices, absorbed attention included
    (``q_nope W_UK`` and ``o W_UV``), experts as the held routes."""
    d, H = m.d_model, m.heads
    attn = (d * H * (m.nope + m.rope) + d * row_width(m)
            + 2 * H * m.nope * m.kv_rank + H * m.v_dim * d)
    n_moe = m.layers - m.dense_layers
    held_routes = m.top_k * m.experts / m.router
    moe = d * m.router + _expert(m) * (held_routes + m.shared)
    return 2.0 * (m.layers * attn + m.dense_layers * 3 * d * m.d_ff
                  + n_moe * moe)


def _attn_flops(m: Spec, n_query_ctx: float) -> float:
    """Absorbed attention of (query, context token) pairs, all layers."""
    return (2.0 * m.layers * m.heads * (2 * m.kv_rank + m.rope)
            * n_query_ctx)


def token_flops(m: Spec, context: int, logits: bool) -> float:
    """Forward operations of one token that attends ``context`` tokens
    (itself included); ``logits`` adds the output head."""
    f = _token_weight_flops(m) + _attn_flops(m, context)
    return f + (2.0 * m.d_model * m.vocab if logits else 0.0)


def prompt_flops(m: Spec, n_prompt: int) -> float:
    """A whole prompt prefilled: causal attention, one set of logits."""
    f = _token_weight_flops(m) * n_prompt
    f += _attn_flops(m, n_prompt * (n_prompt + 1) / 2)
    return f + 2.0 * m.d_model * m.vocab


def _merge_bytes(m: Spec) -> int:
    """One span's exchange per layer: the absorbed query in, the latent
    partial (o, m, l) out."""
    H = m.heads
    return H * row_width(m) * BF16 + H * m.kv_rank * F32 + 2 * H * F32


def decode_attn_work(m: Spec, context: int, spans: int = 1):
    """(operations, bytes) of the paged latent decode attention of one
    token over ``context`` tokens held in ``spans`` pools, all layers:
    read every row of the context once, the query in and the partial out
    per span."""
    flops = _attn_flops(m, context)
    nbytes = m.layers * (context * row_width(m) * BF16
                         + spans * _merge_bytes(m))
    return flops, nbytes


def prefill_attn_work(m: Spec, n_query: int, prefix: int, spans: int = 1):
    """(operations, bytes) of one chunk's paged prefix attention:
    ``n_query`` chunk tokens over ``prefix`` written tokens in ``spans``
    pools, all layers (the chunk's causal part is not counted)."""
    flops = _attn_flops(m, n_query * prefix)
    nbytes = m.layers * (prefix * row_width(m) * BF16
                         + spans * n_query * _merge_bytes(m))
    return flops, nbytes
