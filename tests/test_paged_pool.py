"""Paged pool serving path: paged/dist decode equivalence, metadata-only
KV moves, and the bounded-recompilation guarantee."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.models.prefill as prefill_mod
from repro.configs import get_smoke_config
from repro.kernels.ops import paged_micro_attention
from repro.models.model import decode_step, init_params
from repro.models.prefill import decode_step_dist, decode_step_paged, prefill
from repro.serving import (Cluster, Request, RequestState, SamplingParams,
                           ServingConfig)
from repro.serving.kvpool import (RankKVPool, build_local_tables,
                                  read_pool_rows, table_bucket,
                                  write_pool_rows)


@pytest.fixture(scope="module")
def setup():
    cfg = get_smoke_config("olmo-1b")
    params = init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _greedy_reference(params, cfg, prompt, n_new):
    tokens = jnp.asarray([prompt], jnp.int32)
    logits, state = prefill(params, cfg, tokens,
                            max_len=len(prompt) + n_new + 2)
    out = [int(jnp.argmax(logits[0]))]
    for _ in range(n_new - 1):
        lg, state = decode_step(params, cfg, state,
                                jnp.asarray([out[-1]], jnp.int32))
        out.append(int(jnp.argmax(lg[0])))
    return out


# ------------------------------------------------------------------ #
# kvpool device helpers
# ------------------------------------------------------------------ #
def test_pool_rows_roundtrip():
    L, NB, bs, K, hd = 2, 6, 4, 2, 8
    pool = jnp.zeros((L, NB, bs, K, hd), jnp.float32)
    rows = jax.random.normal(jax.random.PRNGKey(0), (L, 7, K, hd))
    pool = write_pool_rows(pool, [3, 1], rows, bs)
    got = read_pool_rows(pool, [3, 1], bs)
    np.testing.assert_array_equal(np.asarray(got[:, :7]), np.asarray(rows))
    np.testing.assert_array_equal(np.asarray(got[:, 7:]),
                                  np.zeros((L, 1, K, hd)))


def test_table_bucket_is_coarse():
    assert table_bucket(1) == 8 and table_bucket(8) == 8
    assert table_bucket(9) == 16 and table_bucket(100) == 128
    # Any span length maps onto log2-many buckets.
    assert len({table_bucket(n) for n in range(1, 257)}) <= 6


def test_paged_op_backends_agree():
    key = jax.random.PRNGKey(7)
    R, NB, bs, K, G, D, MB = 3, 12, 8, 2, 2, 16, 4
    H = K * G
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (R, H, D))
    pool_k = jax.random.normal(kk, (NB, bs, K, D))
    pool_v = jax.random.normal(kv, (NB, bs, K, D))
    table = jnp.asarray([[0, 3, 5, -1], [7, -1, -1, -1], [2, 4, 6, 8]],
                        jnp.int32)
    tail = jnp.asarray([5, 8, 2], jnp.int32)
    a = paged_micro_attention(q, pool_k, pool_v, table, tail, backend="jnp")
    b = paged_micro_attention(q, pool_k, pool_v, table, tail,
                              backend="pallas", interpret=True)
    for x, y in zip(a, b):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   atol=1e-5, rtol=1e-5)


# ------------------------------------------------------------------ #
# decode_step_paged == decode_step_dist (same tokens, same KV)
# ------------------------------------------------------------------ #
def test_decode_step_paged_matches_dist(setup):
    cfg, params = setup
    key = jax.random.PRNGKey(2)
    B, T, bs = 2, 24, 8
    n_over, maxlen = 8, 16                     # dist ring keeps [8, 24)
    n_local = T - n_over
    tokens = jax.random.randint(key, (B, T + 3), 0, cfg.vocab_size)

    _, full_state = prefill(params, cfg, tokens[:, :T], max_len=T + 8)

    # --- dist path (dense spans + ring), as the serving engine ran it.
    _, ring_state = prefill(params, cfg, tokens[:, :T], max_len=maxlen)
    remote_k = full_state.kv_k[:, :, :n_over + 3]
    remote_v = full_state.kv_v[:, :, :n_over + 3]

    # --- paged path: owner pool holds the tail, creditor pool the prefix.
    L, K, hd = cfg.num_layers, cfg.num_kv_heads, cfg.head_dim
    dt = jnp.dtype(cfg.dtype)
    own_k = jnp.zeros((L, 16, bs, K, hd), dt)
    own_v = jnp.zeros((L, 16, bs, K, hd), dt)
    cred_k = jnp.zeros((L, 16, bs, K, hd), dt)
    cred_v = jnp.zeros((L, 16, bs, K, hd), dt)
    own_pool, cred_pool = RankKVPool(16, bs), RankKVPool(16, bs)
    for b in range(B):
        own_pool.append_tokens(b, n_local)
        blocks = own_pool.requests[b].blocks
        own_k = write_pool_rows(own_k, blocks,
                                full_state.kv_k[:, b, n_over:T], bs)
        own_v = write_pool_rows(own_v, blocks,
                                full_state.kv_v[:, b, n_over:T], bs)
        cred_pool.append_tokens(b, n_over)
        cblocks = cred_pool.requests[b].blocks
        cred_k = write_pool_rows(cred_k, cblocks,
                                 full_state.kv_k[:, b, :n_over], bs)
        cred_v = write_pool_rows(cred_v, cblocks,
                                 full_state.kv_v[:, b, :n_over], bs)

    st = ring_state
    for i, t in enumerate(range(T, T + 3)):
        start_i = T + i + 1 - maxlen
        lg_dist, st = decode_step_dist(
            params, cfg, st, tokens[:, t],
            jnp.full((B,), start_i, jnp.int32), remote_k, remote_v,
            jnp.full((B,), start_i, jnp.int32))

        wblk = np.zeros(B, np.int32)
        woff = np.zeros(B, np.int32)
        for b in range(B):
            own_pool.append_tokens(b, 1)
            rb = own_pool.requests[b]
            wblk[b] = rb.blocks[-1]
            woff[b] = rb.tail_tokens - 1
        needed = max(len(own_pool.requests[b].blocks) for b in range(B))
        tables, tails = build_local_tables([own_pool, cred_pool],
                                           list(range(B)),
                                           table_bucket(needed))
        lg_paged, own_k, own_v = decode_step_paged(
            params, cfg, tokens[:, t], np.full(B, T + i, np.int32),
            own_k, own_v, tables, tails, wblk, woff,
            remote_pools=((cred_k, cred_v),))
        np.testing.assert_allclose(np.asarray(lg_paged, np.float32),
                                   np.asarray(lg_dist, np.float32),
                                   atol=2e-2, rtol=2e-2)


# ------------------------------------------------------------------ #
# Global-pool decode == per-instance paged decode, both on the kernel
# ------------------------------------------------------------------ #
def test_global_decode_kernel_matches_paged_decode(setup):
    """One kernel call over the stacked rank pools (global pool) gives
    the logits of one call per pool (per-instance paged decode), with
    the Pallas kernel in interpret mode on both paths."""
    from repro.serving.sharded_step import decode_step_global
    cfg, params = setup
    B, T, bs, NB, n_over = 2, 40, 8, 16, 16
    tokens = jax.random.randint(jax.random.PRNGKey(5), (B, T + 1), 0,
                                cfg.vocab_size)
    _, full = prefill(params, cfg, tokens[:, :T], max_len=T + 8)
    L, K, hd = cfg.num_layers, cfg.num_kv_heads, cfg.head_dim
    pools = [RankKVPool(NB, bs), RankKVPool(NB, bs)]    # owner, creditor
    kv = [[jnp.zeros((L, NB, bs, K, hd), jnp.dtype(cfg.dtype))
           for _ in range(2)] for _ in pools]
    for b in range(B):
        for p, (t0, t1) in enumerate(((n_over, T), (0, n_over))):
            pools[p].append_tokens(b, t1 - t0)
            blocks = pools[p].requests[b].blocks
            for i, cache in enumerate((full.kv_k, full.kv_v)):
                kv[p][i] = write_pool_rows(kv[p][i], blocks,
                                           cache[:, b, t0:t1], bs)
    # The global step takes tables without the pending token (it joins
    # as a self partial); the paged step writes it first and attends it.
    g_tables, g_tails = build_local_tables(pools, list(range(B)), 8)
    wblk, woff = np.zeros(B, np.int32), np.zeros(B, np.int32)
    for b in range(B):
        pools[0].append_tokens(b, 1)
        wblk[b] = pools[0].requests[b].blocks[-1]
        woff[b] = pools[0].requests[b].tail_tokens - 1
    p_tables, p_tails = build_local_tables(pools, list(range(B)), 8)
    lens = np.full(B, T, np.int32)
    gk = jnp.stack([kv[0][0], kv[1][0]])
    gv = jnp.stack([kv[0][1], kv[1][1]])
    lg_global, _, _ = decode_step_global(
        params, cfg, tokens[:, T], lens, gk, gv, g_tables, g_tails, wblk,
        woff, rank=0, backend="pallas")
    lg_paged, _, _ = decode_step_paged(
        params, cfg, tokens[:, T], lens, kv[0][0], kv[0][1], p_tables,
        p_tails, wblk, woff, remote_pools=((kv[1][0], kv[1][1]),),
        backend="pallas")
    np.testing.assert_allclose(np.asarray(lg_global, np.float32),
                               np.asarray(lg_paged, np.float32),
                               atol=2e-2, rtol=2e-2)


# ------------------------------------------------------------------ #
# A KV move is metadata + pool rows only; logits survive the boundary
# ------------------------------------------------------------------ #
def test_move_is_metadata_only(setup):
    cfg, params = setup
    rng = np.random.default_rng(11)
    prompt = list(rng.integers(0, cfg.vocab_size, size=40))
    n_new = 20
    ref = _greedy_reference(params, cfg, prompt, n_new)

    cl = Cluster(params, cfg, ServingConfig.smoke(
        max_batch=2, pool_blocks=32))
    req = Request(prompt=prompt, sampling=SamplingParams(max_new_tokens=n_new))
    cl.submit(req)

    # Acceptance: no dense-array span/host dicts anywhere in the engines.
    for eng in cl.engines.values():
        assert not hasattr(eng, "remote") and not hasattr(eng, "hosted")
    shapes = {i: (e.pool_k.shape, e.pool_v.shape)
              for i, e in cl.engines.items()}
    total_blocks = {i: e.rmanager.pool.alloc.num_blocks
                    for i, e in cl.engines.items()}

    owner = creditor = None
    moved = False
    for _ in range(200):
        pre_moves = sum(e.stats.moves for e in cl.engines.values())
        cl.step()
        post_moves = sum(e.stats.moves for e in cl.engines.values())
        if not moved and post_moves > pre_moves:
            moved = True
            owner = next(e for e in cl.engines.values()
                         if req.req_id in e.remote_insts)
            creditor = cl.engines[owner.remote_insts[req.req_id][-1]]
            # Pool tensors were edited in place-shape: no new allocations.
            for i, e in cl.engines.items():
                assert (e.pool_k.shape, e.pool_v.shape) == shapes[i]
                assert e.rmanager.pool.alloc.num_blocks == total_blocks[i]
            # The creditor's table now addresses the moved blocks.
            assert creditor.rmanager.is_hosting(req.req_id)
            assert creditor.rmanager.pool.requests[req.req_id].blocks
        if req.done:
            break
    assert moved, "scenario never triggered a KV move"
    assert req.state == RequestState.FINISHED
    # Logits (greedy argmax stream) are unchanged across the move boundary.
    assert req.output == ref


# ------------------------------------------------------------------ #
# Recompiles bounded by table buckets / rank counts, not span growth
# ------------------------------------------------------------------ #
def test_recompile_count_bounded_by_buckets(setup):
    cfg, params = setup
    rng = np.random.default_rng(5)
    # Distinctive shapes so this test's traces are not already cached.
    cl = Cluster(params, cfg, ServingConfig.smoke(
        max_batch=2, max_local_len=12, pool_blocks=24, block_size=4,
        move_chunk_tokens=4, prefill_chunk=32))
    req = Request(prompt=list(rng.integers(0, cfg.vocab_size, size=10)),
                  sampling=SamplingParams(max_new_tokens=26))
    before = prefill_mod.paged_trace_count()
    cl.submit(req)
    cl.run_until_done(max_steps=300)
    traces = prefill_mod.paged_trace_count() - before

    assert req.state == RequestState.FINISHED
    n_moves = sum(e.stats.moves for e in cl.engines.values())
    assert n_moves >= 4, f"wanted >=4 KV moves, got {n_moves}"
    assert 1 <= traces <= 2, \
        f"decode step retraced {traces}x across {n_moves} moves"
