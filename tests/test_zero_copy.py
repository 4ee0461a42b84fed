"""Zero-copy KV hot path (ISSUE-4 acceptance).

(a) Donation guards: the jitted pool updaters and the paged decode /
    prefill steps DONATE the pool tensors — on backends that honor
    donation the returned array reuses the donated buffer (no
    [L, NB, bs, K, hd] copy per step) and the stale handle is dead;
    outputs stay token-identical to the dense pre-donation oracle.
(b) The Pallas prefill-chunk paged partial matches the pure-jnp oracle
    in ``kernels/ref.py`` across chunk sizes (and the jnp fallback).
(c) Async (overlapped) vs serial movement is a pure scheduling choice:
    the decoded token streams are identical, only the sync policy
    differs.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.kernels.ops import paged_prefill_attention
from repro.kernels.ref import paged_prefill_micro_attention_ref
from repro.models.model import decode_step, init_params
from repro.models.prefill import prefill
from repro.serving import (Cluster, InstanceEngine, Request, RequestState,
                           SamplingParams, ServingConfig)
from repro.serving.engine import buffer_ptr
from repro.serving.kvpool import scatter_pool_rows, write_pool_rows

_SETUPS = {}


def _setup(arch="olmo-1b"):
    if arch not in _SETUPS:
        cfg = get_smoke_config(arch)
        _SETUPS[arch] = (cfg, init_params(jax.random.PRNGKey(0), cfg))
    return _SETUPS[arch]


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


def _donation_supported() -> bool:
    """True iff this backend reuses a donated buffer in place."""
    f = jax.jit(lambda x: x + 1, donate_argnums=0)
    x = jnp.zeros((256,), jnp.float32)
    p = buffer_ptr(x)
    y = f(x)
    return p is not None and buffer_ptr(y) == p


# ------------------------------------------------------------------ #
# (b) Pallas prefill-chunk partial == ref oracle, all chunk sizes
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("chunk", [3, 8, 32])
def test_prefill_partial_kernel_matches_oracle(chunk):
    key = jax.random.PRNGKey(11)
    NB, bs, K, G, D, MB = 12, 8, 2, 2, 24, 4      # D off the 128 lane
    H = K * G
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (chunk, H, D))
    pool_k = jax.random.normal(kk, (NB, bs, K, D))
    pool_v = jax.random.normal(kv, (NB, bs, K, D))
    for table, tail in [([0, 3, 5, -1], 5), ([7, -1, -1, -1], 8),
                        ([2, 4, 6, 8], 2)]:
        table = jnp.asarray(table, jnp.int32)
        nblk = jnp.sum(table >= 0)
        ref = paged_prefill_micro_attention_ref(
            q, pool_k, pool_v, table, nblk, jnp.asarray(tail, jnp.int32))
        got_pl = paged_prefill_attention(
            q, pool_k, pool_v, table, jnp.asarray(tail, jnp.int32),
            backend="pallas", interpret=True)
        got_np = paged_prefill_attention(
            q, pool_k, pool_v, table, jnp.asarray(tail, jnp.int32),
            backend="jnp")
        for r, a, b in zip(ref, got_pl, got_np):
            np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                       atol=1e-5, rtol=1e-5)
            np.testing.assert_allclose(np.asarray(b), np.asarray(r),
                                       atol=1e-5, rtol=1e-5)


def test_prefill_partial_kernel_empty_table_is_identity():
    """A rank with zero coverage contributes the merge identity."""
    q = jax.random.normal(jax.random.PRNGKey(0), (4, 4, 16))
    pool = jnp.ones((6, 8, 2, 16))
    table = jnp.full((4,), -1, jnp.int32)
    o, m, l = paged_prefill_attention(q, pool, pool, table,
                                      jnp.asarray(8, jnp.int32),
                                      backend="pallas", interpret=True)
    assert float(jnp.abs(o).sum()) == 0.0
    assert bool(jnp.all(jnp.isneginf(m)))
    assert float(jnp.abs(l).sum()) == 0.0


# ------------------------------------------------------------------ #
# (a) Donation guards
# ------------------------------------------------------------------ #
def test_pool_writers_donate_and_kill_stale_handle():
    if not _donation_supported():
        pytest.skip("backend does not honor donation")
    L, NB, bs, K, hd = 2, 6, 4, 2, 8
    pool = jnp.zeros((L, NB, bs, K, hd), jnp.float32)
    rows = jax.random.normal(jax.random.PRNGKey(1), (L, 7, K, hd))
    p0 = buffer_ptr(pool)
    new = write_pool_rows(pool, [3, 1], rows, bs)
    assert buffer_ptr(new) == p0, "write_pool_rows copied the pool"
    assert pool.is_deleted(), "stale pool handle survived donation"
    p1 = buffer_ptr(new)
    new2 = scatter_pool_rows(new, [2, 2], [0, 1], rows[:, :2])
    assert buffer_ptr(new2) == p1, "scatter_pool_rows copied the pool"
    assert new.is_deleted()


def test_decode_steps_never_copy_the_pool_and_match_oracle():
    """The whole serving hot path — streaming admission chunks + every
    decode step — runs without one pool-tensor copy, and the generated
    stream equals the dense pre-donation oracle."""
    cfg, params = _setup()
    rng = np.random.default_rng(21)
    prompt = list(rng.integers(0, cfg.vocab_size, 21))
    n_new = 12
    ref = _greedy_reference(params, cfg, prompt, n_new)

    eng = InstanceEngine(params, cfg, max_batch=2, max_local_len=64,
                         pool_blocks=32, block_size=8, prefill_chunk=8)
    req = Request(prompt=prompt,
                  sampling=SamplingParams(max_new_tokens=n_new))
    eng.submit(req)
    for _ in range(40):
        if req.done:
            break
        eng.step()
    assert req.state == RequestState.FINISHED
    assert req.output == ref, "donated hot path diverged from oracle"
    assert eng.stats.decode_steps >= n_new - 1
    if _donation_supported():
        assert eng.stats.pool_copy_steps == 0, \
            f"{eng.stats.pool_copy_steps}/{eng.stats.decode_steps} " \
            "decode steps copied the pool despite donation"


def test_sampling_key_is_threaded_not_reuploaded():
    """The PRNG key is split device-side and donated: stochastic
    sampling stays reproducible across engines, and on donating
    backends the key buffer is reused in place every step."""
    cfg, params = _setup()
    rng = np.random.default_rng(5)
    prompt = list(rng.integers(0, cfg.vocab_size, 6))

    def run():
        eng = InstanceEngine(params, cfg, max_batch=2, max_local_len=64,
                             pool_blocks=32, block_size=8,
                             prefill_chunk=8, inst_id=0)
        req = Request(prompt=prompt, sampling=SamplingParams(
            max_new_tokens=8, temperature=0.8))
        eng.submit(req)
        ptrs = set()
        for _ in range(20):
            if req.done:
                break
            eng.step()
            p = buffer_ptr(eng._key)
            if p is not None:
                ptrs.add(p)
        return req.output, ptrs

    out_a, ptrs_a = run()
    out_b, _ = run()
    assert out_a == out_b, "device-side key threading broke determinism"
    if _donation_supported():
        assert len(ptrs_a) == 1, \
            "sampling key was re-uploaded instead of donated in place"


# ------------------------------------------------------------------ #
# (c) Async vs serial movement: token-identical, only sync policy
# ------------------------------------------------------------------ #
def test_async_and_serial_movement_are_token_identical():
    # float32 so LSE-merge rounding cannot flip near-tie argmaxes of the
    # random-init smoke model (same convention as the striped-scheduling
    # exactness tests — the comparison is token identity, not numerics).
    import dataclasses
    cfg = dataclasses.replace(get_smoke_config("olmo-1b"),
                              dtype="float32")
    params = init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(13)
    prompts = [list(rng.integers(0, cfg.vocab_size, 40)),
               list(rng.integers(0, cfg.vocab_size, 24))]
    n_new = 16
    refs = [_greedy_reference(params, cfg, p, n_new) for p in prompts]

    outs, movers = [], []
    for overlap in (False, True):
        cl = Cluster(params, cfg, ServingConfig.smoke(
            max_batch=2, pool_blocks=32, async_movement=overlap))
        reqs = [Request(prompt=p,
                        sampling=SamplingParams(max_new_tokens=n_new))
                for p in prompts]
        for r in reqs:
            cl.submit(r)
        cl.run_until_done(max_steps=400)
        assert all(r.state == RequestState.FINISHED for r in reqs)
        outs.append([r.output for r in reqs])
        moved = sum(e.stats.moves for e in cl.engines.values())
        movers.append(moved)
        assert cl.stager.staged > 0, "movement never went through staging"
        if overlap:
            # Overlap mode: strictly fewer sync points than copy chains.
            assert cl.stager.synced < cl.stager.staged
        else:
            assert cl.stager.synced == cl.stager.staged
    assert movers[0] > 0 and movers[1] > 0, "scenario moved no KV"
    assert outs[0] == outs[1], "sync policy changed the token stream"
    assert outs[1] == refs, "movement path diverged from dense oracle"
