"""Prefill-into-cache and the paged / distributed decode steps (dense/moe).

``prefill`` runs the full-sequence forward while capturing per-layer KV
(and recurrent states) into a ``DecodeState`` so generation can continue
token-by-token. On the dense/moe SERVING path it is no longer the
admission step: ``prefill_chunk_paged`` streams a prompt into the block
pools chunk-by-chunk — each fixed-shape step runs the causal core over
the chunk plus a paged MicroAttention partial over every already-written
pool span (local + creditors), LSE-merges them, and scatters the chunk's
KV rows straight into pre-reserved blocks. Peak admission memory is
O(chunk + pool) and compile shapes never depend on prompt length;
``prefill`` remains the hybrid/ssm admission path and the equivalence
oracle for the chunked pipeline.

``decode_step_paged`` is the serving data path: every request's KV lives
in fixed-shape block pools (``pool_k/pool_v: [L, NB, bs, K, hd]`` per
rank) and is addressed purely through block tables. One local pool is
updated in place (the new token's KV is scattered into its tail block);
any number of remote (creditor) pools are read-only. Each rank's paged
MicroAttention partial (paper Eq. 2) is LSE-merged (Eq. 3) — tables are
bucketed by the caller so the step compiles O(#buckets * #rank-counts)
times, never per sequence length.

``decode_step_dist`` is the older dense-span formulation (local ring +
concatenated remote arrays); it remains as an equivalence oracle for the
paged path and for the mesh/collective version in
``repro.serving.sharded_step``.
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.online_softmax import (combine, finalize,
                                       micro_attention_decode,
                                       micro_attention_prefill)
from repro.kernels.ops import resolve_backend
from repro.models.attention import make_causal_core, qkv_project
from repro.models.common import apply_ffn, apply_norm
from repro.models.model import (DecodeState, _attn_layer_fwd, _rglru_layer_fwd,
                                embed_tokens, init_decode_state, unembed)
from repro.models.moe import apply_moe
from repro.models.xlstm import (MLstmState, SLstmState, apply_mlstm_block,
                                apply_slstm_block)


# ===================================================================== #
# Prefill
# ===================================================================== #
def _ring_fill(cache, k, T, maxlen):
    """Write the last min(T, maxlen) tokens of k [B,T,K,hd] into ring cache
    [B, maxlen, K, hd] at slots (abs_pos % maxlen)."""
    n = min(T, maxlen)
    p0 = T - n
    abs_pos = p0 + jnp.arange(n)
    slots = abs_pos % maxlen
    return cache.at[:, slots].set(k[:, p0:p0 + n])


def prefill(params, cfg: ModelConfig, tokens=None, embeds=None, *,
            max_len: int, backend: str = "xla", chunk: int = 512,
            capacity_factor: float = -1.0,
            ) -> Tuple[jax.Array, DecodeState]:
    """Uniform-length prefill. Returns (logits_last [B,V], DecodeState).

    The DecodeState local cache keeps the LAST min(T, max_len) tokens
    (ring layout); the caller is responsible for placing the overflowed
    prefix [0, T-max_len) on creditor instances (``start`` bookkeeping
    lives in the serving runtime).
    """
    B, T = (tokens.shape if embeds is None else embeds.shape[:2])
    positions = jnp.arange(T, dtype=jnp.int32)[None].repeat(B, 0)
    x = embed_tokens(params, cfg, tokens, embeds, positions)
    core = make_causal_core(cfg, backend=backend, chunk=chunk)
    state = init_decode_state(cfg, B, max_len)
    lens = jnp.full((B,), T, jnp.int32)

    if cfg.family in ("dense", "moe"):
        def make_body(moe):
            def body(x, lp):
                x, kv, _ = _attn_layer_fwd(lp, x, positions, cfg, core,
                                           moe=moe,
                                           capacity_factor=capacity_factor)
                return x, kv
            return body
        if cfg.family == "dense":
            x, (ks, vs) = jax.lax.scan(make_body(False), x, params["layers"])
        else:
            nd = cfg.first_k_dense
            kds = vds = None
            if nd:
                x, (kds, vds) = jax.lax.scan(make_body(False), x,
                                             params["dense_layers"])
            x, (kms, vms) = jax.lax.scan(make_body(True), x,
                                         params["moe_layers"])
            ks = jnp.concatenate([kds, kms], 0) if nd else kms
            vs = jnp.concatenate([vds, vms], 0) if nd else vms
        # ks: [L, B, T, K, hd] -> ring-fill each layer.
        fill = jax.vmap(lambda c, k: _ring_fill(c, k, T, max_len))
        state = state._replace(kv_k=fill(state.kv_k, ks),
                               kv_v=fill(state.kv_v, vs), lens=lens)

    elif cfg.family == "hybrid":
        pat = cfg.block_pattern
        wcore = make_causal_core(cfg, backend=backend, chunk=chunk,
                                 window=cfg.local_window)
        w = state.kv_k.shape[2]

        def gbody(x, gp):
            kvs = []
            rec = []
            for j, kind in enumerate(pat):
                lp = gp[f"{j}_{kind}"]
                if kind == "rglru":
                    x, st = _rglru_layer_fwd(lp, x, cfg)
                    rec.append(st)
                else:
                    x, kv, _ = _attn_layer_fwd(lp, x, positions, cfg, wcore)
                    kvs.append(kv)
            return x, (kvs, rec)
        x, (kvs, rec) = jax.lax.scan(gbody, x, params["groups"])
        # kvs: list (per attn slot in pattern) of (k [G,B,T,K,hd], v).
        n_left = cfg.num_layers - (cfg.num_layers // len(pat)) * len(pat)
        left_rec = []
        if n_left:
            for j, kind in enumerate(pat[:n_left]):
                lp = params["leftover"][f"{j}_{kind}"]
                assert kind == "rglru"
                x, st = _rglru_layer_fwd(lp, x, cfg)
                left_rec.append(st)
        ks = jnp.concatenate([kv[0] for kv in kvs], 0)   # [n_attn,B,T,K,hd]
        vs = jnp.concatenate([kv[1] for kv in kvs], 0)
        fill = jax.vmap(lambda c, k: _ring_fill(c, k, T, w))
        # rec from the group scan: each element r = (conv [ng,B,3,w], h
        # [ng,B,w]); leftover layers contribute unstacked (B,...) states.
        convs = [r[0] for r in rec] + [r[0][None] for r in left_rec]
        hs = [r[1] for r in rec] + [r[1][None] for r in left_rec]
        conv = jnp.concatenate(convs, 0)
        h = jnp.concatenate(hs, 0)
        state = state._replace(kv_k=fill(state.kv_k, ks),
                               kv_v=fill(state.kv_v, vs),
                               lens=lens, rec=(conv, h))

    elif cfg.family == "ssm":
        def gbody(x, gp):
            def mbody(x, mlp):
                hh = apply_norm(mlp["ln"], x, cfg)
                y, st = apply_mlstm_block(mlp["blk"], hh, cfg)
                return x + y, tuple(st)
            x, mst = jax.lax.scan(mbody, x, gp["mlstm"])
            hh = apply_norm(gp["slstm"]["ln"], x, cfg)
            y, sst = apply_slstm_block(gp["slstm"]["blk"], hh, cfg)
            return x + y, (mst, tuple(sst))
        x, (mst, sst) = jax.lax.scan(gbody, x, params["groups"])
        state = state._replace(lens=lens,
                               rec={"mlstm": MLstmState(*mst),
                                    "slstm": SLstmState(*sst)})
    else:
        raise ValueError(cfg.family)

    logits = unembed(params, cfg, x[:, -1])
    return logits, state


# ===================================================================== #
# Slot management (engine batches individual prefills into fixed slots)
# ===================================================================== #
def repack_ring(state: DecodeState, new_maxlen: int,
                n_keep: Optional[int] = None) -> DecodeState:
    """Convert a full prefill cache (max_len = T, identity layout) into a
    ring cache of ``new_maxlen`` holding the tail ``n_keep`` tokens.

    Only the non-pooled serving path (hybrid/ssm engines) uses this; the
    dense/moe path writes prefill KV straight into the block pool.
    """
    T = int(state.lens[0])
    n = min(T, new_maxlen if n_keep is None else n_keep)
    k = state.kv_k[:, :, T - n:T]
    v = state.kv_v[:, :, T - n:T]
    slots = (T - n + jnp.arange(n)) % new_maxlen
    L, B = state.kv_k.shape[:2]
    shape = (L, B, new_maxlen) + state.kv_k.shape[3:]
    nk = jnp.zeros(shape, state.kv_k.dtype).at[:, :, slots].set(k)
    nv = jnp.zeros(shape, state.kv_v.dtype).at[:, :, slots].set(v)
    return DecodeState(nk, nv, state.lens, state.rec)


def batch_axis_map(cfg: ModelConfig):
    """Batch-axis index for each DecodeState field's arrays."""
    if cfg.family in ("dense", "moe"):
        return {"kv": 1, "rec": None}
    if cfg.family == "hybrid":
        return {"kv": 1, "rec": 1}
    return {"kv": None, "rec": {"mlstm": 2, "slstm": 1}}


def write_slot(state: DecodeState, slot: int, req: DecodeState,
               cfg: ModelConfig) -> DecodeState:
    """Copy a single-request (B=1) DecodeState into batch slot ``slot``."""
    ax = batch_axis_map(cfg)

    def put(dst, src, axis):
        idx = [slice(None)] * dst.ndim
        idx[axis] = slot
        src_idx = [slice(None)] * src.ndim
        src_idx[axis] = 0
        return dst.at[tuple(idx)].set(src[tuple(src_idx)])

    kv_k, kv_v, rec = state.kv_k, state.kv_v, state.rec
    if state.kv_k is not None:
        # Ring layouts may differ if max_len differs; require equal here.
        assert state.kv_k.shape[2] == req.kv_k.shape[2], \
            "slot and request cache sizes must match"
        kv_k = put(state.kv_k, req.kv_k, ax["kv"])       # [L, B, ...]
        kv_v = put(state.kv_v, req.kv_v, ax["kv"])
    if state.rec is not None:
        if cfg.family == "hybrid":
            rec = (put(state.rec[0], req.rec[0], ax["rec"]),  # [n_rg,B,3,w]
                   put(state.rec[1], req.rec[1], ax["rec"]))
        else:
            rec = {
                "mlstm": MLstmState(*[put(d, s, ax["rec"]["mlstm"])
                                      for d, s in zip(state.rec["mlstm"],
                                                      req.rec["mlstm"])]),
                "slstm": SLstmState(*[put(d, s, ax["rec"]["slstm"])
                                      for d, s in zip(state.rec["slstm"],
                                                      req.rec["slstm"])]),
            }
    lens = state.lens.at[slot].set(req.lens[0])
    return DecodeState(kv_k, kv_v, lens, rec)


# ===================================================================== #
# Distributed decode step (dense/moe): local ring span + remote spans
# ===================================================================== #
def _ring_mask(length, start, maxlen):
    """[B, maxlen] validity for ring slots holding abs pos in [start, len).

    ``length``: [B] sequence length AFTER the current token's write. Slot j
    holds absolute position p = (len-1) - ((len-1-j) mod maxlen); it is
    valid iff p >= max(start, 0).
    """
    j = jnp.arange(maxlen, dtype=jnp.int32)[None]
    last = (length - 1)[:, None]
    p = last - ((last - j) % maxlen)
    return (p >= start[:, None]) & (p >= 0)


def _dist_attn_decode(lp, x, ck, cv, lens, start, rk, rv, rlen, cfg):
    """Local ring partial + remote span partial, merged (paper Eq. 3)."""
    B = x.shape[0]
    q, k, v = qkv_project(lp, x, lens[:, None], cfg)
    ql = q[:, 0]
    maxlen = ck.shape[1]
    slot = lens % maxlen
    ck = ck.at[jnp.arange(B), slot].set(k[:, 0])
    cv = cv.at[jnp.arange(B), slot].set(v[:, 0])
    lmask = _ring_mask(lens + 1, jnp.maximum(start, 0), maxlen)
    local = micro_attention_decode(ql, ck, cv, lmask)
    rmask = (jnp.arange(rk.shape[1], dtype=jnp.int32)[None]
             < rlen[:, None])
    remote = micro_attention_decode(ql, rk, rv, rmask)
    o, m, l = combine(local, remote)
    out = finalize(o, l)
    out = out.reshape(B, 1, -1).astype(x.dtype) @ lp["wo"]
    return out, ck, cv


def decode_step_dist(params, cfg: ModelConfig, state: DecodeState,
                     tokens: jax.Array, start: jax.Array,
                     remote_k: jax.Array, remote_v: jax.Array,
                     remote_len: jax.Array
                     ) -> Tuple[jax.Array, DecodeState]:
    """DistAttention decode for dense/moe: KV = local[start, len) + remote.

    remote_k/v: [L, B, S_r, K, hd] concatenated creditor spans (token
    positions [0, start)); remote_len: [B] valid remote tokens.
    """
    assert cfg.family in ("dense", "moe"), "only attention archs pool KV"
    lens = state.lens
    x = embed_tokens(params, cfg, tokens[:, None], None,
                     positions=lens[:, None])

    def make_body(moe):
        def body(x, xs):
            lp, ck, cv, rk, rv = xs
            h = apply_norm(lp["ln1"], x, cfg)
            out, ck, cv = _dist_attn_decode(lp["attn"], h, ck, cv, lens,
                                            start, rk, rv, remote_len, cfg)
            x = x + out
            h = apply_norm(lp["ln2"], x, cfg)
            if moe:
                x = x + apply_moe(lp["moe"], h, cfg, capacity_factor=-1.0)
            else:
                x = x + apply_ffn(lp["ffn"], h, cfg)
            return x, (ck, cv)
        return body

    if cfg.family == "dense":
        x, (ck, cv) = jax.lax.scan(
            make_body(False), x,
            (params["layers"], state.kv_k, state.kv_v, remote_k, remote_v))
    else:
        nd = cfg.first_k_dense
        if nd:
            x, (ckd, cvd) = jax.lax.scan(
                make_body(False), x,
                (params["dense_layers"], state.kv_k[:nd], state.kv_v[:nd],
                 remote_k[:nd], remote_v[:nd]))
        x, (ckm, cvm) = jax.lax.scan(
            make_body(True), x,
            (params["moe_layers"], state.kv_k[nd:], state.kv_v[nd:],
             remote_k[nd:], remote_v[nd:]))
        ck = jnp.concatenate([ckd, ckm], 0) if nd else ckm
        cv = jnp.concatenate([cvd, cvm], 0) if nd else cvm

    logits = unembed(params, cfg, x[:, 0])
    return logits, DecodeState(ck, cv, lens + 1, None)


# ===================================================================== #
# Paged decode step (dense/moe): KV pool + block tables, fixed shapes
# ===================================================================== #
# Incremented once per trace of the jitted paged step; serving tests use
# it to assert the recompile count is bounded by the table buckets and
# rank counts, never by remote-span length.
_PAGED_TRACE_COUNT = 0


def paged_trace_count() -> int:
    return _PAGED_TRACE_COUNT


def note_paged_trace() -> None:
    """Count one trace of a paged decode step (any attention family)."""
    global _PAGED_TRACE_COUNT
    _PAGED_TRACE_COUNT += 1


def _paged_partial(q, pk, pv, table, tail, backend):
    """One rank's MicroAttention partial over its pool (paper Eq. 2)."""
    if backend == "pallas":
        from repro.kernels.ops import paged_micro_attention
        return paged_micro_attention(q, pk, pv, table, tail,
                                     backend="pallas")
    from repro.kernels.ops import paged_micro_attention_jnp
    return paged_micro_attention_jnp(q, pk, pv, table, tail)


def _scan_dense_moe(params, cfg, x, pool_k, pool_v, remote_k, remote_v,
                    make_body):
    """Layer-stack scan shared by the paged decode and prefill steps.

    ``make_body(moe)`` returns a scan body consuming
    ``(x, (lp, pk, pv, rks, rvs))``; per-layer pool slices (and the
    remote tuples) are split across the dense/moe sub-stacks and the
    scan outputs re-concatenated along the layer axis.
    """
    if cfg.family == "dense":
        return jax.lax.scan(make_body(False), x,
                            (params["layers"], pool_k, pool_v,
                             remote_k, remote_v))
    nd = cfg.first_k_dense
    ys_d = None
    if nd:
        x, ys_d = jax.lax.scan(
            make_body(False), x,
            (params["dense_layers"], pool_k[:nd], pool_v[:nd],
             tuple(a[:nd] for a in remote_k),
             tuple(a[:nd] for a in remote_v)))
    x, ys_m = jax.lax.scan(
        make_body(True), x,
        (params["moe_layers"], pool_k[nd:], pool_v[nd:],
         tuple(a[nd:] for a in remote_k),
         tuple(a[nd:] for a in remote_v)))
    if nd:
        ys_m = jax.tree.map(lambda a, b: jnp.concatenate([a, b], 0),
                            ys_d, ys_m)
    return x, ys_m


def _paged_attn_decode(lp, x, lens, pk, pv, rks, rvs, tables, tails,
                       write_block, write_off, cfg, backend):
    """Paged DistAttention for one layer: write tail token, merge ranks.

    pk/pv: [NB, bs, K, hd] — the LOCAL pool's layer slice (updated);
    rks/rvs: tuples of remote layer slices (read-only);
    tables: [P, B, MB] block tables (rank 0 = local); tails: [P, B].
    """
    B = x.shape[0]
    q, k, v = qkv_project(lp, x, lens[:, None], cfg)
    ql = q[:, 0]
    # Append this step's KV into each request's tail block. Inactive
    # slots carry an out-of-range block index; mode="drop" skips them.
    pk = pk.at[write_block, write_off].set(k[:, 0].astype(pk.dtype),
                                           mode="drop")
    pv = pv.at[write_block, write_off].set(v[:, 0].astype(pv.dtype),
                                           mode="drop")
    part = _paged_partial(ql, pk, pv, tables[0], tails[0], backend)
    for p, (rk, rv) in enumerate(zip(rks, rvs), start=1):
        part = combine(part, _paged_partial(ql, rk, rv, tables[p],
                                            tails[p], backend))
    out = finalize(part[0], part[2])
    out = out.reshape(B, 1, -1).astype(x.dtype) @ lp["wo"]
    return out, pk, pv


@functools.partial(jax.jit, static_argnames=("cfg", "backend"),
                   donate_argnames=("pool_k", "pool_v"))
def _decode_step_paged_jit(params, tokens, lens, pool_k, pool_v,
                           remote_k, remote_v, tables, tails,
                           write_block, write_off, *, cfg, backend):
    note_paged_trace()
    x = embed_tokens(params, cfg, tokens[:, None], None,
                     positions=lens[:, None])

    def make_body(moe):
        def body(x, xs):
            lp, pk, pv, rks, rvs = xs
            h = apply_norm(lp["ln1"], x, cfg)
            out, pk, pv = _paged_attn_decode(
                lp["attn"], h, lens, pk, pv, rks, rvs, tables, tails,
                write_block, write_off, cfg, backend)
            x = x + out
            h = apply_norm(lp["ln2"], x, cfg)
            if moe:
                x = x + apply_moe(lp["moe"], h, cfg, capacity_factor=-1.0)
            else:
                x = x + apply_ffn(lp["ffn"], h, cfg)
            return x, (pk, pv)
        return body

    x, (pk, pv) = _scan_dense_moe(params, cfg, x, pool_k, pool_v,
                                  remote_k, remote_v, make_body)
    logits = unembed(params, cfg, x[:, 0])
    return logits, pk, pv


def decode_step_paged(params, cfg: ModelConfig, tokens, lens,
                      pool_k: jax.Array, pool_v: jax.Array,
                      tables, tails, write_block, write_off,
                      remote_pools: Sequence[Tuple[jax.Array, jax.Array]]
                      = (), *, backend: Optional[str] = None
                      ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Fixed-shape paged DistAttention decode (dense/moe serving path).

    tokens/lens: [B] (lens = absolute position of the new token);
    pool_k/pool_v: [L, NB, bs, K, hd] — the owner rank's pool, DONATED
    into the step: the caller must drop its handles and continue with
    the returned arrays, which on donating backends are the same device
    buffers updated in place (KV for the new token is written into the
    request's tail block before attention so the token attends to
    itself);
    tables/tails: [P, B, MB] / [P, B] from ``build_local_tables`` over
    (owner pool, *creditor pools) with a bucketed MB;
    write_block/write_off: [B] target (block id, offset) of the new
    token in the OWNER pool; inactive slots use block id NB (dropped);
    remote_pools: creditor [L, NB_p, bs, K, hd] pool pairs, read-only.

    All shapes are independent of context length: growing a request — or
    migrating its blocks between ranks — only edits table/pool *contents*,
    so the step retraces only when the table bucket or rank count changes.
    Returns (logits [B, V], new_pool_k, new_pool_v).
    """
    assert cfg.family in ("dense", "moe"), "only attention archs pool KV"
    backend = resolve_backend(backend)
    remote_k = tuple(pk for pk, _ in remote_pools)
    remote_v = tuple(pv for _, pv in remote_pools)
    return _decode_step_paged_jit(
        params, jnp.asarray(tokens, jnp.int32), jnp.asarray(lens, jnp.int32),
        pool_k, pool_v, remote_k, remote_v,
        jnp.asarray(tables, jnp.int32), jnp.asarray(tails, jnp.int32),
        jnp.asarray(write_block, jnp.int32),
        jnp.asarray(write_off, jnp.int32), cfg=cfg, backend=backend)


# ===================================================================== #
# Chunked paged prefill (dense/moe): stream a prompt into block pools
# ===================================================================== #
_PREFILL_CHUNK_TRACE_COUNT = 0


def prefill_chunk_trace_count() -> int:
    return _PREFILL_CHUNK_TRACE_COUNT


def note_prefill_chunk_trace() -> None:
    """Count one trace of a paged prefill chunk step (any family)."""
    global _PREFILL_CHUNK_TRACE_COUNT
    _PREFILL_CHUNK_TRACE_COUNT += 1


def _chunk_attn_paged(lp, x, positions, valid, pk, pv, rks, rvs,
                      tables, tails, write_block, write_off, cfg, backend):
    """One layer of the streaming-prefill step for one prompt chunk.

    Every chunk query attends to (a) the tokens already streamed into the
    pools — one paged MicroAttention partial per rank over ``tables``,
    which address exactly the written prefix [0, t0) — and (b) the chunk
    itself under the causal mask. Partials LSE-merge (paper Eq. 3), so
    the result equals dense full-prefix attention. The chunk's KV rows
    landing on THIS rank are scattered into the local pool before the
    paged partial runs; the pre-chunk tables mask them out, so they are
    seen only by the chunk-internal causal partial.
    """
    B, C = x.shape[:2]
    q, k, v = qkv_project(lp, x, positions, cfg)
    pk = pk.at[write_block, write_off].set(k[0].astype(pk.dtype),
                                           mode="drop")
    pv = pv.at[write_block, write_off].set(v[0].astype(pv.dtype),
                                           mode="drop")

    def rank_partial(p, rk, rv):
        # All C chunk queries share the rank's ONE prefix table. On the
        # Pallas path the dedicated prefill kernel streams blocks through
        # VMEM (nothing gathers); the jnp path gathers the prefix rows
        # once and runs a shared-KV partial (transient O(prefix), never
        # O(chunk x prefix)). Both live in kernels.ops.
        from repro.kernels.ops import paged_prefill_attention
        return paged_prefill_attention(q[0], rk, rv, tables[p, 0],
                                       tails[p, 0], backend=backend)

    part = rank_partial(0, pk, pv)
    for p, (rk, rv) in enumerate(zip(rks, rvs), start=1):
        part = combine(part, rank_partial(p, rk, rv))
    o_c, m_c, l_c = micro_attention_prefill(q, k, v, positions, positions,
                                            valid)
    part = combine(part, (o_c[0], m_c[0], l_c[0]))
    out = finalize(part[0], part[2])
    out = out.reshape(B, C, -1).astype(x.dtype) @ lp["wo"]
    return out, pk, pv, k[0], v[0]


@functools.partial(jax.jit, static_argnames=("cfg", "backend"),
                   donate_argnames=("pool_k", "pool_v"))
def _prefill_chunk_paged_jit(params, tokens, positions, valid, last_idx,
                             pool_k, pool_v, remote_k, remote_v,
                             tables, tails, write_block, write_off, *,
                             cfg, backend):
    note_prefill_chunk_trace()
    x = embed_tokens(params, cfg, tokens, None, positions)

    def make_body(moe):
        def body(x, xs):
            lp, pk, pv, rks, rvs = xs
            h = apply_norm(lp["ln1"], x, cfg)
            out, pk, pv, k, v = _chunk_attn_paged(
                lp["attn"], h, positions, valid, pk, pv, rks, rvs,
                tables, tails, write_block, write_off, cfg, backend)
            x = x + out
            h = apply_norm(lp["ln2"], x, cfg)
            if moe:
                x = x + apply_moe(lp["moe"], h, cfg, capacity_factor=-1.0)
            else:
                x = x + apply_ffn(lp["ffn"], h, cfg)
            return x, (pk, pv, k, v)
        return body

    x, (pk, pv, ks, vs) = _scan_dense_moe(params, cfg, x, pool_k, pool_v,
                                          remote_k, remote_v, make_body)
    logits = unembed(params, cfg, jnp.take(x, last_idx, axis=1))
    return logits, pk, pv, ks, vs


def prefill_chunk_paged(params, cfg: ModelConfig, tokens, t0: int,
                        n_valid: int, pool_k: jax.Array, pool_v: jax.Array,
                        tables, tails, write_block, write_off,
                        remote_pools: Sequence[Tuple[jax.Array, jax.Array]]
                        = (), *, backend: Optional[str] = None):
    """One fixed-shape streaming-prefill step over prompt chunk [t0, t0+C).

    tokens: [C] chunk token ids (the final chunk is zero-padded; only the
    first ``n_valid`` entries are real); pool_k/pool_v: the owner rank's
    [L, NB, bs, K, hd] pool, DONATED — continue with the returned
    arrays (in-place row updates on donating backends), never the
    passed handles; tables/tails: [P, 1, MB] / [P, 1] from ``prefix_tables``
    addressing the already-written tokens [0, t0) on (owner,
    *creditors); write_block/write_off: [C] OWNER-pool target of each
    chunk token (block id NB for rows bound for a creditor or padding —
    dropped); remote_pools: creditor pool pairs, read-only.

    Every shape is a function of (C, P, MB bucket, pool dims) — never of
    the prompt length — so admission compiles are bounded by chunk size
    and peak extra device memory is O(chunk), not O(T). Returns
    (logits [1, V] at the last valid chunk position, new_pool_k,
    new_pool_v, k_chunk [L, C, K, hd], v_chunk) — the chunk KV export is
    what the engine streams to creditor pools for prefix rows.
    """
    assert cfg.family in ("dense", "moe"), "only attention archs pool KV"
    backend = resolve_backend(backend)
    C = len(tokens)
    positions = t0 + jnp.arange(C, dtype=jnp.int32)[None]
    valid = (jnp.arange(C, dtype=jnp.int32) < n_valid)[None]
    remote_k = tuple(pk for pk, _ in remote_pools)
    remote_v = tuple(pv for _, pv in remote_pools)
    return _prefill_chunk_paged_jit(
        params, jnp.asarray(tokens, jnp.int32)[None], positions, valid,
        jnp.asarray(n_valid - 1, jnp.int32), pool_k, pool_v,
        remote_k, remote_v, jnp.asarray(tables, jnp.int32),
        jnp.asarray(tails, jnp.int32), jnp.asarray(write_block, jnp.int32),
        jnp.asarray(write_off, jnp.int32), cfg=cfg, backend=backend)
