"""Plain float32 reference of the served models, and its control.

The published decoder, written out in ``jax.numpy`` at float32 with the
highest matmul precision: embedding; per layer a pre-norm (RMSNorm with
scale, or LayerNorm with no parameters), multi-head attention with
grouped KV heads and rotary positions (half-split rotation), a SwiGLU
feed-forward; final norm; output head (tied: the embedding). No kernel,
cache, batching or code of the program. Weights are drawn again from the
seed, layer by layer (``harness.weights``), in the served dtype and then
widened, so the reference sees exactly the numbers the server holds.

It runs once the window has closed and the server is freed, one layer at
a time over every sampled sequence, padded to a fixed length so that its
programs compile once per cell.

``gaps`` returns, for every served token, how far that token's reference
logit lies below the reference's best logit at that position. With
``control=True`` it also returns the same gap for the token that the
control puts first: the same reference computed in float8 (e4m3), the
precision below the bfloat16 the configurations serve in: both operands
of every matmul rounded to float8 (weights with one scale per output
column, activations, q, k, v and the attention weights with one per
row), accumulated in float32.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from harness.spec import ModelSpec
from harness.weights import (layer_key, layer_weights, outer_weights,
                             root_key)

HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 256        # query rows per attention block
ROW_BLOCK = 2048     # rows per feed-forward block
HEAD_ROWS = 128      # positions per output-head block
FP8_MAX = 448.0      # largest float8_e4m3fn


def _mm(a, b):
    return jnp.matmul(a, b, precision=HI)


def fp8_round(w: jax.Array, axis: int = 0) -> jax.Array:
    """Round to float8 e4m3 with one scale per slice along ``axis`` (a
    weight matrix's output column: ``axis=0``; an activation's row:
    ``axis=-1``), and widen back to float32."""
    s = jnp.max(jnp.abs(w), axis=axis, keepdims=True) / FP8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (w / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _rows(quant: bool):
    """What a matmul's activation operand goes through: float8 rows for
    the control, nothing for the reference."""
    return (lambda a: fp8_round(a, -1)) if quant else (lambda a: a)


def _norm(x, scale, m: ModelSpec):
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
def _layer_weights_f32(key, m: ModelSpec, quant: bool):
    w = {k: v.astype(jnp.float32) for k, v in layer_weights(key, m).items()}
    if quant:
        for k in ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down"):
            w[k] = fp8_round(w[k])
    return w


@functools.partial(jax.jit, static_argnames=("m", "quant"),
                   donate_argnames=("x",))
def _layer(x, w, *, m: ModelSpec, quant: bool):
    """One decoder layer over x [N, S, d] (causal, positions 0..S-1);
    ``quant`` rounds every matmul's activation operand to float8 rows."""
    N, S, d = x.shape
    H, K, hd = m.heads, m.kv_heads, m.head_dim
    G = H // K
    r = _rows(quant)
    h = r(_norm(x, w.get("attn_norm"), m))
    q = r(_rope(_mm(h, w["wq"]).reshape(N, S, H, hd), m.rope_theta))
    k = r(_rope(_mm(h, w["wk"]).reshape(N, S, K, hd), m.rope_theta))
    v = _mm(h, w["wv"]).reshape(N, S, K, hd)
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
    x = x + _mm(r(o), w["wo"])
    h = r(_norm(x, w.get("ffn_norm"), m)).reshape(-1, ROW_BLOCK, d)

    def ffn(hb):
        g = _mm(hb, w["w_gate"])
        return _mm(r(jax.nn.silu(g) * _mm(hb, w["w_up"])), w["w_down"])

    return x + jax.lax.map(ffn, h).reshape(N, S, d)


@functools.partial(jax.jit, static_argnames=("m",))
def _embed(key, tokens, *, m: ModelSpec):
    return outer_weights(key, m)["embed"].astype(jnp.float32)[tokens]


@functools.partial(jax.jit, static_argnames=("m", "quant"))
def _head(key, *, m: ModelSpec, quant: bool):
    """(final norm scale or None, output head [d, V]) in float32."""
    o = outer_weights(key, m)
    w = (o["embed"].T if m.tied else o["unembed"]).astype(jnp.float32)
    return o.get("final_norm"), (fp8_round(w) if quant else w)


@functools.partial(jax.jit, static_argnames=("m", "quant"))
def _logits(x_flat, idx, scale, head, *, m: ModelSpec, quant: bool):
    return _mm(_rows(quant)(_norm(x_flat[idx], scale, m)), head)


@jax.jit
def _gap_of(logits, tok):
    return jnp.max(logits, -1) - jnp.take_along_axis(
        logits, tok[:, None], axis=-1)[:, 0]


def gaps(m: ModelSpec, seed: int,
         seqs: Sequence[Tuple[Sequence[int], Sequence[int]]],
         pad_to: int, *, control: bool = False
         ) -> Dict[str, np.ndarray]:
    """Reference gaps of every served token of ``seqs``.

    ``seqs``: (prompt, served tokens) pairs; the reference reads
    ``prompt + served[:-1]``, and served token i is judged by the
    logits at position ``len(prompt) - 1 + i``. Returns ``{"served":
    gaps}`` and, with ``control``, ``{"control": gaps}`` of the tokens
    the float8 reference puts first at the same positions.
    """
    N = len(seqs)
    assert pad_to % ROW_BLOCK == 0 and pad_to % Q_BLOCK == 0
    tokens = np.zeros((N, pad_to), np.int32)
    idx, served = [], []
    for j, (prompt, out) in enumerate(seqs):
        seq = list(prompt) + list(out[:-1])
        assert len(seq) <= pad_to, (len(seq), pad_to)
        tokens[j, :len(seq)] = seq
        T = len(prompt)
        idx += [j * pad_to + T - 1 + i for i in range(len(out))]
        served += list(out)
    key = root_key(seed)
    okey = layer_key(key, -1)
    streams = [False, True] if control else [False]
    xs = {q: _embed(okey, jnp.asarray(tokens), m=m) for q in streams}
    for layer in range(m.layers):
        lk = layer_key(key, layer)
        for q in streams:
            w = _layer_weights_f32(lk, m, q)
            xs[q] = _layer(xs[q], w, m=m, quant=q)
            del w
    M = len(idx)
    pad = -M % HEAD_ROWS
    idx = np.asarray(idx + [0] * pad, np.int32)
    tok = np.asarray(served + [0] * pad, np.int32)
    out: Dict[str, List[np.ndarray]] = {"served": [], "control": []}
    heads = {q: _head(okey, m=m, quant=q) for q in streams}
    flat = {q: xs[q].reshape(N * pad_to, m.d_model) for q in streams}
    for r in range(0, len(idx), HEAD_ROWS):
        i = jnp.asarray(idx[r:r + HEAD_ROWS])
        ref = _logits(flat[False], i, *heads[False], m=m, quant=False)
        out["served"].append(np.asarray(
            _gap_of(ref, jnp.asarray(tok[r:r + HEAD_ROWS]))))
        if control:
            low = _logits(flat[True], i, *heads[True], m=m, quant=True)
            first = jnp.argmax(low, -1).astype(jnp.int32)
            out["control"].append(np.asarray(_gap_of(ref, first)))
    res = {k: np.concatenate(v)[:M] for k, v in out.items() if v}
    return res


def widest(g: Optional[np.ndarray]) -> float:
    """The widest gap (0 for no tokens)."""
    return float(np.max(g)) if g is not None and g.size else 0.0
