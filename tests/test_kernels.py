"""Pallas kernels vs ref.py oracles: shape/dtype sweeps, allclose."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.ops import flash_prefill, paged_micro_attention
from repro.core.online_softmax import micro_attention_decode


def _rand(key, shape, dtype):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


@pytest.mark.parametrize("B,S,H,K,D", [
    (1, 128, 4, 4, 16),      # MHA
    (2, 256, 8, 2, 32),      # GQA
    (1, 200, 4, 1, 112),     # MQA, ragged seq, unaligned head dim
    (1, 64, 3, 3, 8),        # odd head count
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_prefill_matches_ref(B, S, H, K, D, dtype):
    key = jax.random.PRNGKey(42)
    kq, kk, kv = jax.random.split(key, 3)
    q = _rand(kq, (B, S, H, D), dtype)
    k = _rand(kk, (B, S, K, D), dtype)
    v = _rand(kv, (B, S, K, D), dtype)
    got = flash_prefill(q, k, v, bq=64, bk=64, interpret=True)
    want = ref.flash_prefill_ref(q, k, v)
    tol = 3e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("window", [16, 64])
def test_flash_prefill_sliding_window(window):
    key = jax.random.PRNGKey(7)
    kq, kk, kv = jax.random.split(key, 3)
    B, S, H, K, D = 1, 128, 4, 2, 16
    q = _rand(kq, (B, S, H, D), jnp.float32)
    k = _rand(kk, (B, S, K, D), jnp.float32)
    v = _rand(kv, (B, S, K, D), jnp.float32)
    got = flash_prefill(q, k, v, window=window, bq=32, bk=32, interpret=True)
    want = ref.flash_prefill_ref(q, k, v, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def _make_pool(key, R, NB, bs, K, D, MB, dtype, rng, rows=None):
    """Random pool + tables with variable block counts and tail lengths;
    ``rows`` fixes each row's (block count, tail length) instead."""
    kk, kv = jax.random.split(key)
    pool_k = _rand(kk, (NB, bs, K, D), dtype)
    pool_v = _rand(kv, (NB, bs, K, D), dtype)
    table = -np.ones((R, MB), np.int32)
    if rows is None:
        nblk = rng.integers(0, MB + 1, size=R)
    else:
        nblk = np.asarray([n for n, _ in rows])
    tail = np.ones((R,), np.int32)
    perm = rng.permutation(NB)
    used = 0
    for r in range(R):
        n = int(nblk[r])
        take = perm[used:used + n]
        if len(take) < n:          # pool exhausted; shrink
            n = len(take)
            nblk[r] = n
        table[r, :n] = take
        used += n
        if rows is not None:
            tail[r] = rows[r][1]
        else:
            tail[r] = rng.integers(1, bs + 1) if n else bs
    return pool_k, pool_v, jnp.asarray(table), jnp.asarray(nblk, jnp.int32), \
        jnp.asarray(tail)


@pytest.mark.parametrize("R,NB,bs,K,G,D,MB,rows", [
    pytest.param(4, 16, 16, 2, 2, 16, 4, None, id="4-16-16-2-2-16-4"),
    pytest.param(3, 32, 8, 1, 4, 32, 8, None,          # MQA
                 id="3-32-8-1-4-32-8"),
    pytest.param(2, 8, 32, 4, 1, 112, 3, None,         # MHA, unaligned D
                 id="2-8-32-4-1-112-3"),
    # Fixed (nblk, tail) rows over several compute tiles. The kernel's
    # tile (pb slots) comes from the block's bytes: 16 (bf16) / 8 (f32)
    # for nemo-like GQA blocks, 8 / 4 for olmo-like MHA blocks. Rows span
    # several tiles, end mid-tile or on a tile's edge, have a tail of 1
    # or of bs, or are empty next to full rows; MB is no multiple of pb.
    pytest.param(4, 96, 16, 8, 4, 128, 40,
                 ((40, 16), (0, 16), (17, 1), (16, 16)), id="gqa-tiles"),
    pytest.param(4, 48, 16, 16, 1, 128, 20,
                 ((0, 16), (20, 1), (9, 16), (1, 1)), id="mha-tiles"),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_micro_attention_matches_ref(R, NB, bs, K, G, D, MB, rows,
                                           dtype):
    H = K * G
    rng = np.random.default_rng(0)
    key = jax.random.PRNGKey(3)
    kq, kp = jax.random.split(key)
    q = _rand(kq, (R, H, D), dtype)
    pool_k, pool_v, table, nblk, tail = _make_pool(kp, R, NB, bs, K, D, MB,
                                                   dtype, rng, rows)
    got_o, got_m, got_l = paged_micro_attention(q, pool_k, pool_v, table,
                                                tail, interpret=True)
    want_o, want_m, want_l = ref.paged_micro_attention_ref(
        q, pool_k, pool_v, table, nblk, tail)
    tol = 5e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(np.asarray(got_m), np.asarray(want_m),
                               atol=tol, rtol=tol)
    np.testing.assert_allclose(np.asarray(got_l), np.asarray(want_l),
                               atol=tol, rtol=tol)
    np.testing.assert_allclose(np.asarray(got_o), np.asarray(want_o),
                               atol=tol, rtol=tol)
    empty = np.asarray(nblk) == 0           # the merge identity, exactly
    assert (np.asarray(got_o)[empty] == 0).all()
    assert np.isneginf(np.asarray(got_m)[empty]).all()
    assert (np.asarray(got_l)[empty] == 0).all()


@pytest.mark.parametrize("block,max_blocks,want", [
    (16 * 8 * 128 * 2, 1024, 16),     # mistral-nemo-12b bf16 blocks
    (16 * 16 * 128 * 2, 128, 8),      # olmo-1b bf16 blocks
    (16 * 16 * 128 * 2, 3, 2),        # capped by a narrow table
    (4 << 20, 64, 1),                 # a block larger than a tile
])
def test_decode_tile_blocks_from_shapes(block, max_blocks, want):
    from repro.kernels.ops import _decode_tile_blocks
    assert _decode_tile_blocks(block, max_blocks) == want


def test_paged_partial_merges_to_full_attention():
    """Kernel partials from two disjoint pools == full attention (Eq. 2+3)."""
    from repro.core.online_softmax import combine, finalize
    key = jax.random.PRNGKey(9)
    R, bs, K, G, D = 2, 8, 2, 2, 16
    H = K * G
    S = 64                                   # 8 blocks, split 5 / 3
    kq, kk, kv = jax.random.split(key, 3)
    q = _rand(kq, (R, H, D), jnp.float32)
    k = _rand(kk, (R, S, K, D), jnp.float32)
    v = _rand(kv, (R, S, K, D), jnp.float32)

    ref_out = finalize(*(lambda p: (p[0], p[2]))(
        micro_attention_decode(q, k, v, jnp.ones((R, S), bool))),
    ) if False else None
    from repro.core.attention import full_attention_decode
    ref_out = full_attention_decode(q, k, v, jnp.ones((R, S), bool))

    kb = k.reshape(R, 8, bs, K, D)
    vb = v.reshape(R, 8, bs, K, D)
    parts = []
    for blocks in (range(0, 5), range(5, 8)):
        idx = list(blocks)
        pool_k = kb[:, idx].reshape(-1, bs, K, D)
        pool_v = vb[:, idx].reshape(-1, bs, K, D)
        table = jnp.asarray(
            [[r * len(idx) + i for i in range(len(idx))] for r in range(R)],
            jnp.int32)
        tail = jnp.full((R,), bs, jnp.int32)
        parts.append(paged_micro_attention(q, pool_k, pool_v, table, tail,
                                           interpret=True))
    merged = combine(parts[0], parts[1])
    out = finalize(merged[0], merged[2])
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_out),
                               atol=1e-4, rtol=1e-4)
