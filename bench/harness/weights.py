"""Random model weights, made on the device from the run's seed.

One function, ``layer_weights``, draws one layer's named matrices from a
key; the server's whole parameter tree is made in one jitted call that
maps it over the layers, and the reference draws each layer again, alone,
from the same key (``layer_key``). So both see the same numbers, and the
reference takes nothing that the program has made.
"""
from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from harness.spec import ModelSpec

EMBED_STD = 0.02     # input embedding; the residual norms rescale it
NORM_STD = 0.1       # norm scales are 1 + NORM_STD * N(0, 1)


def root_key(seed: int) -> jax.Array:
    """A raw threefry key from a seed of any size (two 32-bit words)."""
    words = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jnp.asarray(words, jnp.uint32)


def layer_key(key: jax.Array, layer: int) -> jax.Array:
    """Key of layer ``layer``; layer -1 is the embedding and the head."""
    return jax.random.fold_in(key, layer + 1)


def _normal(key, shape, std, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def _scale(key, d):
    """A norm scale, 1 + NORM_STD * N(0, 1), its noise rounded to
    bfloat16 so that every compilation of it gives the same floats."""
    return 1.0 + _normal(key, (d,), NORM_STD, jnp.bfloat16).astype(
        jnp.float32)


def layer_weights(key: jax.Array, m: ModelSpec) -> Dict[str, jax.Array]:
    """One transformer layer: attention, SwiGLU feed-forward, norm scales.

    Matrices have std ``fan_in ** -0.5`` and the served dtype; RMSNorm
    scales are float32 (a LayerNorm without parameters has none).
    """
    d, H, K, hd, F = m.d_model, m.heads, m.kv_heads, m.head_dim, m.d_ff
    dt = jnp.dtype(m.dtype)
    ks = jax.random.split(key, 9)
    w = {
        "wq": _normal(ks[0], (d, H * hd), d ** -0.5, dt),
        "wk": _normal(ks[1], (d, K * hd), d ** -0.5, dt),
        "wv": _normal(ks[2], (d, K * hd), d ** -0.5, dt),
        "wo": _normal(ks[3], (H * hd, d), (H * hd) ** -0.5, dt),
        "w_gate": _normal(ks[4], (d, F), d ** -0.5, dt),
        "w_up": _normal(ks[5], (d, F), d ** -0.5, dt),
        "w_down": _normal(ks[6], (F, d), F ** -0.5, dt),
    }
    if m.norm == "rmsnorm":
        w["attn_norm"] = _scale(ks[7], d)
        w["ffn_norm"] = _scale(ks[8], d)
    return w


def outer_weights(key: jax.Array, m: ModelSpec) -> Dict[str, jax.Array]:
    """Embedding, final norm scale and (untied) output head."""
    dt = jnp.dtype(m.dtype)
    ks = jax.random.split(key, 3)
    w = {"embed": _normal(ks[0], (m.vocab, m.d_model), EMBED_STD, dt)}
    if m.norm == "rmsnorm":
        w["final_norm"] = _scale(ks[1], m.d_model)
    if not m.tied:
        w["unembed"] = _normal(ks[2], (m.d_model, m.vocab),
                               m.d_model ** -0.5, dt)
    return w


def _norm_params(w, name):
    return {"scale": w[name]} if name in w else {}


@functools.partial(jax.jit, static_argnames=("m",))
def program_params(key: jax.Array, m: ModelSpec):
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
