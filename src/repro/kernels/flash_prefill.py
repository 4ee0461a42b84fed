"""Pallas TPU kernel: causal (optionally sliding-window) flash attention.

Used for the prefill phase and training attention. Online-softmax over KV
tiles; fp32 accumulators in VMEM scratch.

TPU mapping:
  grid = (B, H, nq, nk) with nk innermost/sequential; q tile (bq, D) and
  KV tile (bk, D) are MXU-shaped (128 x 128-padded-D by default).
  Operands are head-major [B, H, S, D] (the ops.py wrapper transposes)
  so each tile is a (1, 1, bq, D) block whose last two dims Mosaic can
  tile; a (1, bq, 1, D) block of [B, S, H, D] is refused.
  GQA: the kv-head block index is h // (H // K) — computed in the
  BlockSpec index map, so each query head streams only its group's KV.
  Causal skip: tiles entirely above the diagonal (and entirely outside
  the sliding window) are skipped with ``pl.when`` — ~2x fewer tiles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = float("-inf")


def _kernel(q_ref, k_ref, v_ref, o_ref, acc, m_s, l_s,
            *, bq: int, bk: int, nk: int, seq: int, scale: float,
            window: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)

    q_lo = iq * bq
    k_lo = ik * bk
    # Tile-level causal/window culling (static per grid step).
    live = k_lo <= q_lo + bq - 1
    if window:
        live = jnp.logical_and(live, k_lo + bk - 1 > q_lo - window)

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)                  # [bq, D]
        k = k_ref[0, 0].astype(jnp.float32)                  # [bk, D]
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        qp = q_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kp = k_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        ok = (kp <= qp) & (kp < seq)
        if window:
            ok = ok & (kp > qp - window)
        s = jnp.where(ok, s, NEG_INF)
        m_blk = jnp.max(s, axis=-1)                          # [bq]
        m_old = m_s[:, 0]
        m_new = jnp.maximum(m_old, m_blk)
        alpha = jnp.where(jnp.isneginf(m_old), 0.0, jnp.exp(m_old - m_new))
        p = jnp.exp(s - jnp.where(jnp.isneginf(m_new), 0.0, m_new)[:, None])
        p = jnp.where(ok, p, 0.0)
        l_s[:, 0] = l_s[:, 0] * alpha + jnp.sum(p, -1)
        acc[...] = acc[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_s[:, 0] = m_new

    @pl.when(ik == nk - 1)
    def _finalize():
        l = l_s[:, 0]
        denom = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc[...] / denom[:, None]).astype(o_ref.dtype)


def flash_prefill_kernel(
    q: jax.Array,          # [B, H, S, D] (S and D pre-padded by ops.py)
    k: jax.Array,          # [B, K, S, D]
    v: jax.Array,
    *,
    seq: int,              # true (unpadded) sequence length
    scale: float,
    window: int = 0,
    bq: int = 128,
    bk: int = 128,
    interpret: bool,
):
    B, H, S, D = q.shape
    K = k.shape[1]
    G = H // K
    bq = min(bq, S)
    bk = min(bk, S)
    nq, nk = S // bq, S // bk

    kernel = functools.partial(_kernel, bq=bq, bk=bk, nk=nk, seq=seq,
                               scale=scale, window=window)
    return pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, bk, D),
                         lambda b, h, iq, ik: (b, h // G, ik, 0)),
            pl.BlockSpec((1, 1, bk, D),
                         lambda b, h, iq, ik: (b, h // G, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, D),
                               lambda b, h, iq, ik: (b, h, iq, 0)),
        scratch_shapes=[
            pltpu.VMEM((bq, D), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        out_shape=jax.ShapeDtypeStruct((B, H, S, D), q.dtype),
        interpret=interpret,
    )(q, k, v)
