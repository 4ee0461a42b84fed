"""Seeds and random draws that every architecture's weights share.

An architecture (``archs/<arch>.py``) draws each layer's named matrices
from ``layer_key(root_key(seed), layer)``: its program parameter tree is
made in one jitted call that maps the layer draw over the layers, and its
reference draws each layer again, alone, from the same key. So both see
the same numbers, and the reference takes nothing that the program has
made.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

EMBED_STD = 0.02     # input embedding; the residual norms rescale it
NORM_STD = 0.1       # norm scales are 1 + NORM_STD * N(0, 1)


def root_key(seed: int) -> jax.Array:
    """A raw threefry key from a seed of any size (two 32-bit words)."""
    words = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jnp.asarray(words, jnp.uint32)


def layer_key(key: jax.Array, layer: int) -> jax.Array:
    """Key of layer ``layer``; layer -1 is the embedding and the head."""
    return jax.random.fold_in(key, layer + 1)


def normal(key, shape, std, dtype) -> jax.Array:
    """N(0, std^2) drawn in float32, then cast to ``dtype``."""
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def matrix(key, fan_in: int, fan_out: int, dtype) -> jax.Array:
    """A [fan_in, fan_out] weight with std ``fan_in ** -0.5``."""
    return normal(key, (fan_in, fan_out), fan_in ** -0.5, dtype)


def norm_scale(key, d: int) -> jax.Array:
    """A norm scale, 1 + NORM_STD * N(0, 1), its noise rounded to
    bfloat16 so that every compilation of it gives the same floats."""
    return 1.0 + normal(key, (d,), NORM_STD, jnp.bfloat16).astype(
        jnp.float32)
