"""What every architecture's plain reference shares: float32 matmuls at
the highest precision, the float8 control's rounding, the packing of
the sampled sequences, and the blocked output head that turns the last
hidden states into the gaps that decide ``correct``.

An architecture's ``gaps(spec, seed, seqs, pad_to, control)`` runs its
own layers over ``pack(seqs, pad_to)``'s tokens, one layer at a time,
and hands ``head_gaps`` a function that gives the logits of a block of
positions. It returns, for every served token, how far that token's
reference logit lies below the reference's best logit at that position;
with ``control=True`` also the same gap for the token that the control
puts first: the same reference computed in float8 (e4m3), the precision
below the bfloat16 the configurations serve in: both operands of every
matmul rounded to float8 (weights with one scale per output column,
activations and attention operands with one per row), accumulated in
float32.
"""
from __future__ import annotations

from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
HEAD_ROWS = 128      # positions per output-head block
FP8_MAX = 448.0      # largest float8_e4m3fn

Seqs = Sequence[Tuple[Sequence[int], Sequence[int]]]


def mm(a, b):
    """A float32 matmul at the highest precision."""
    return jnp.matmul(a, b, precision=HI)


def fp8_round(w: jax.Array, axis: int = 0) -> jax.Array:
    """Round to float8 e4m3 with one scale per slice along ``axis`` (a
    weight matrix's output column: ``axis=0``; an activation's row:
    ``axis=-1``), and widen back to float32."""
    s = jnp.max(jnp.abs(w), axis=axis, keepdims=True) / FP8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (w / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def rows(quant: bool):
    """What a matmul's activation operand goes through: float8 rows for
    the control, nothing for the reference."""
    return (lambda a: fp8_round(a, -1)) if quant else (lambda a: a)


def streams(control: bool) -> List[bool]:
    """The ``quant`` flags to run: the reference, and the control."""
    return [False, True] if control else [False]


def pack(seqs: Seqs, pad_to: int) -> Tuple[np.ndarray, List[int], List[int]]:
    """(tokens [N, pad_to], flat positions, served tokens) of ``seqs``.

    ``seqs``: (prompt, served tokens) pairs; the reference reads
    ``prompt + served[:-1]``, and served token i is judged by the
    logits at position ``len(prompt) - 1 + i`` (flat index ``j *
    pad_to + len(prompt) - 1 + i`` of sequence j).
    """
    tokens = np.zeros((len(seqs), pad_to), np.int32)
    idx, served = [], []
    for j, (prompt, out) in enumerate(seqs):
        seq = list(prompt) + list(out[:-1])
        assert len(seq) <= pad_to, (len(seq), pad_to)
        tokens[j, :len(seq)] = seq
        T = len(prompt)
        idx += [j * pad_to + T - 1 + i for i in range(len(out))]
        served += list(out)
    return tokens, idx, served


@jax.jit
def _gap_of(logits, tok):
    return jnp.max(logits, -1) - jnp.take_along_axis(
        logits, tok[:, None], axis=-1)[:, 0]


def head_gaps(logits: Callable[[jax.Array, bool], jax.Array],
              idx: List[int], served: List[int], control: bool
              ) -> Dict[str, np.ndarray]:
    """``{"served": gaps}`` (and ``{"control": gaps}``) of the flat
    positions ``idx``, ``HEAD_ROWS`` at a time; ``logits(i, quant)``
    gives the reference's (or the control's) logits at positions ``i``."""
    M = len(idx)
    pad = -M % HEAD_ROWS
    idx = np.asarray(idx + [0] * pad, np.int32)
    tok = np.asarray(served + [0] * pad, np.int32)
    out: Dict[str, List[np.ndarray]] = {"served": [], "control": []}
    for r in range(0, len(idx), HEAD_ROWS):
        i = jnp.asarray(idx[r:r + HEAD_ROWS])
        ref = logits(i, False)
        out["served"].append(np.asarray(
            _gap_of(ref, jnp.asarray(tok[r:r + HEAD_ROWS]))))
        if control:
            first = jnp.argmax(logits(i, True), -1).astype(jnp.int32)
            out["control"].append(np.asarray(_gap_of(ref, first)))
    return {k: np.concatenate(v)[:M] for k, v in out.items() if v}


def widest(g: Optional[np.ndarray]) -> float:
    """The widest gap (0 for no tokens)."""
    return float(np.max(g)) if g is not None and g.size else 0.0
