"""Latent attention (MLA) on the paged serving path, at a tiny size,
against a plain float32 reference written out here (expanded heads, no
cache, no absorption, YaRN as DeepSeek-V2 publishes it).

The tiny preset keeps every mechanism of DeepSeek-V2-Lite: a leading
dense layer, then expert layers routed by a softmax over all experts
whose kept gates are not renormalised, one shared expert, an instance
that holds half the routed experts, and YaRN with an original context
of 16 tokens, shorter than every prompt here, so that the ramp between
interpolated and extrapolated frequencies and the softmax mscale both
act.

Tolerances: program and reference both run in float32, but the program
attends in the absorbed form (``q_nope W_UK`` against the latent row),
merges per-rank partials by their log-sum-exp and sums in another
order; on this preset that moves a logit by at most 7.6e-6 (read on
the CPU), so logits are compared at 1e-4 absolute, thirteen times that
and still far below the spread of the logits (about 1).
"""
import dataclasses
import math
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.serving.engine as engine_mod
from repro.configs.base import ModelConfig
from repro.kernels import ops
from repro.models import mla
from repro.models.model import init_params
from repro.serving import (Cluster, InstanceEngine, Request, RequestState,
                           SamplingParams, ServingConfig)
from repro.serving.hosttier import HostKVTier

HI = jax.lax.Precision.HIGHEST
ATOL = 1e-4          # see the module docstring

TINY = ModelConfig(
    name="tiny-mla", family="mla", num_layers=3, d_model=64, num_heads=4,
    d_ff=96, vocab_size=128, num_experts=8, num_shared_experts=1, top_k=2,
    moe_d_ff=32, first_k_dense=1, norm_topk_prob=False, held_experts=(0, 4),
    kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    rope_theta=10_000.0, rope_factor=4.0, rope_original_max_pos=16,
    rope_beta_fast=32.0, rope_beta_slow=1.0, rope_mscale=0.707,
    rope_mscale_all_dim=0.707, norm_eps=1e-6, dtype="float32")


def _params(cfg=TINY, seed=0):
    """Seeded weights; norm scales perturbed so that they matter."""
    p = init_params(jax.random.PRNGKey(seed), cfg)
    key = jax.random.PRNGKey(seed + 1)

    def perturb(path, a):
        if jax.tree_util.keystr(path).endswith("['scale']"):
            k = jax.random.fold_in(
                key, zlib.crc32(jax.tree_util.keystr(path).encode()) % 2**31)
            return a + 0.1 * jax.random.normal(k, a.shape, a.dtype)
        return a
    return jax.tree_util.tree_map_with_path(perturb, p)


# ------------------------------------------------------------------ #
# The plain reference
# ------------------------------------------------------------------ #
def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _yarn(cfg, T):
    """cos, sin [T, rope] as DeepSeek-V2's YaRN rotary embedding."""
    dim, base, f = cfg.qk_rope_head_dim, cfg.rope_theta, cfg.rope_factor
    orig = cfg.rope_original_max_pos

    def cdim(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) / (
            2 * math.log(base))
    lo = max(math.floor(cdim(cfg.rope_beta_fast)), 0)
    hi = min(math.ceil(cdim(cfg.rope_beta_slow)), dim - 1)
    extra = 1.0 / base ** (np.arange(0, dim, 2) / dim)
    inter = 1.0 / (f * base ** (np.arange(0, dim, 2) / dim))
    mask = 1.0 - np.clip((np.arange(dim // 2) - lo) / (hi - lo), 0, 1)
    inv = inter * (1 - mask) + extra * mask
    freqs = np.outer(np.arange(T), inv)
    emb = np.concatenate([freqs, freqs], -1)

    def ms(m):
        return 0.1 * m * math.log(f) + 1.0
    r = ms(cfg.rope_mscale) / ms(cfg.rope_mscale_all_dim)
    return (jnp.asarray(np.cos(emb) * r, jnp.float32),
            jnp.asarray(np.sin(emb) * r, jnp.float32))


def _rotate(x, cos, sin):
    """DeepSeek-V2's apply_rotary_pos_emb on x [T, h, rope]."""
    T, h, d = x.shape
    x = x.reshape(T, h, d // 2, 2).swapaxes(-1, -2).reshape(T, h, d)
    rot = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos[:, None] + rot * sin[:, None]


def _reference_logits(params, cfg, tokens):
    """Logits [T, V] of the whole sequence ``tokens``."""
    T = len(tokens)
    H, n, rp, r, v = (cfg.num_heads, cfg.qk_nope_head_dim,
                      cfg.qk_rope_head_dim, cfg.kv_lora_rank,
                      cfg.v_head_dim)
    cos, sin = _yarn(cfg, T)
    m = 0.1 * cfg.rope_mscale_all_dim * math.log(cfg.rope_factor) + 1.0
    scale = (n + rp) ** -0.5 * m * m
    mm = lambda a, b: jnp.matmul(a, b, precision=HI)   # noqa: E731
    x = params["embed"][jnp.asarray(tokens)]
    layers = [jax.tree.map(lambda a, i=i: a[i], params["dense_layers"])
              for i in range(cfg.first_k_dense)]
    layers += [jax.tree.map(lambda a, i=i: a[i], params["moe_layers"])
               for i in range(cfg.num_layers - cfg.first_k_dense)]
    for i, lp in enumerate(layers):
        a = lp["attn"]
        h = _rms(x, lp["ln1"]["scale"], cfg.norm_eps)
        if cfg.q_lora_rank:
            q = mm(_rms(mm(h, a["wq_a"]), a["q_norm"]["scale"], cfg.norm_eps),
                   a["wq_b"])
        else:
            q = mm(h, a["wq"])
        q = q.reshape(T, H, n + rp)
        kv = mm(h, a["wkv_a"])
        c = _rms(kv[:, :r], a["kv_norm"]["scale"], cfg.norm_eps)
        k_pe = _rotate(kv[:, None, r:], cos, sin)
        kvb = mm(c, a["wkv_b"]).reshape(T, H, n + v)
        qh = jnp.concatenate([q[..., :n], _rotate(q[..., n:], cos, sin)], -1)
        kh = jnp.concatenate([kvb[..., :n],
                              jnp.broadcast_to(k_pe, (T, H, rp))], -1)
        s = jnp.einsum("qhd,khd->hqk", qh, kh, precision=HI) * scale
        s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)
        o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1),
                       kvb[..., n:], precision=HI)
        x = x + mm(o.reshape(T, H * v), a["wo"])
        h = _rms(x, lp["ln2"]["scale"], cfg.norm_eps)
        if i < cfg.first_k_dense:
            f = lp["ffn"]
            x = x + mm(jax.nn.silu(mm(h, f["wi"])) * mm(h, f["wg"]), f["wo"])
            continue
        x = x + _reference_moe(lp["moe"], h, cfg)
    x = _rms(x, params["final_norm"]["scale"], cfg.norm_eps)
    return mm(x, params["unembed"])


def _reference_moe(p, h, cfg):
    """Every held expert on every row, weighted by its gate: the softmax
    over all routers' outputs, zero off each row's top-k and off the
    experts not held; shared experts always."""
    mm = lambda a, b: jnp.matmul(a, b, precision=HI)   # noqa: E731
    probs = jax.nn.softmax(mm(h, p["router"]), -1)
    top, idx = jax.lax.top_k(probs, cfg.top_k)
    if cfg.norm_topk_prob:
        top = top / top.sum(-1, keepdims=True)
    gates = jnp.zeros_like(probs).at[jnp.arange(h.shape[0])[:, None],
                                     idx].set(top)
    lo, n = cfg.expert_range
    y = 0.0
    for e in range(n):
        w = p["experts"]
        ye = mm(jax.nn.silu(mm(h, w["wi"][e])) * mm(h, w["wg"][e]),
                w["wo"][e])
        y = y + gates[:, lo + e:lo + e + 1] * ye
    for e in range(cfg.num_shared_experts):
        w = p["shared"]
        y = y + mm(jax.nn.silu(mm(h, w["wi"][e])) * mm(h, w["wg"][e]),
                   w["wo"][e])
    return y


# ------------------------------------------------------------------ #
# Serving through the paged latent pool
# ------------------------------------------------------------------ #
def _record_logits(monkeypatch):
    """Every decode step's and every last prefill chunk's logits."""
    seen = []
    for name in ("decode_step_latent", "prefill_chunk_latent"):
        orig = getattr(engine_mod, name)

        def wrapped(*a, _orig=orig, _name=name, **k):
            out = _orig(*a, **k)
            seen.append((_name, np.asarray(out[0])))
            return out
        monkeypatch.setattr(engine_mod, name, wrapped)
    return seen


def _served_logits(seen, req, slot=0):
    """Per output token, the logits that produced it: the final prefill
    chunk's, then each decode step's row of the request's slot."""
    rows, last_prefill = [], None
    for name, lg in seen:
        if name == "prefill_chunk_latent":
            last_prefill = lg[0]
        else:
            if last_prefill is not None:
                rows.append(last_prefill)
                last_prefill = None
            rows.append(lg[slot])
    if last_prefill is not None:
        rows.append(last_prefill)
    return np.stack(rows[:len(req.output)])


def _check_against_reference(params, cfg, req, logits):
    seq = list(req.prompt) + list(req.output[:-1])
    ref = np.asarray(_reference_logits(params, cfg, seq))
    T = len(req.prompt)
    want = ref[T - 1:T - 1 + len(req.output)]
    np.testing.assert_allclose(logits, want, atol=ATOL, rtol=0)
    assert req.output == [int(t) for t in want.argmax(-1)]


@pytest.mark.parametrize("T,spans,q_lora", [(20, False, 0), (45, True, 0),
                                            (45, True, 24)],
                         ids=["local", "spanning-a-creditor", "query-lora"])
def test_prefill_then_decode_matches_reference(T, spans, q_lora,
                                               monkeypatch):
    cfg = dataclasses.replace(TINY, q_lora_rank=q_lora)
    params = _params(cfg)
    seen = _record_logits(monkeypatch)
    cl = Cluster(params, cfg, ServingConfig.smoke(n_instances=2))
    rng = np.random.default_rng(T)
    req = Request(prompt=rng.integers(0, cfg.vocab_size, T).tolist(),
                  sampling=SamplingParams(max_new_tokens=10))
    cl.submit(req)
    cl.step()
    owner = next(e for e in cl.engines.values() if req in e.running)
    slot = req.slot
    assert bool(owner.remote_insts.get(req.req_id)) == spans
    cl.run_until_done(max_steps=100)
    assert req.state == RequestState.FINISHED
    _check_against_reference(params, cfg, req,
                             _served_logits(seen, req, slot))
    counts = cl.tracer.counter_totals()
    steps = len(req.output) - 1
    n_moe = TINY.num_layers - TINY.first_k_dense
    assert counts["count.moe.routed"] == steps * TINY.top_k * n_moe
    assert 0 < counts["count.moe.held"] < counts["count.moe.routed"]
    partials = counts["count.attn.creditor_partials"]
    assert (partials > 0) == spans
    assert partials <= steps * TINY.num_layers


def test_moved_block_decodes_identically(monkeypatch):
    """A prompt just under the local quota: decode growth moves latent
    blocks onto the creditor, and the logits still match the reference."""
    params = _params(seed=3)
    seen = _record_logits(monkeypatch)
    cl = Cluster(params, TINY, ServingConfig.smoke(n_instances=2))
    rng = np.random.default_rng(5)
    req = Request(prompt=rng.integers(0, TINY.vocab_size, 22).tolist(),
                  sampling=SamplingParams(max_new_tokens=14))
    cl.submit(req)
    cl.step()
    owner = next(e for e in cl.engines.values() if req in e.running)
    slot = req.slot
    assert not owner.remote_insts.get(req.req_id)
    cl.run_until_done(max_steps=100)
    assert sum(e.stats.moves for e in cl.engines.values()) >= 1
    assert req.state == RequestState.FINISHED
    _check_against_reference(params, TINY, req,
                             _served_logits(seen, req, slot))


def _decode(eng, req, n):
    out = []
    for _ in range(n):
        eng.step()
        out.append(req.output[-1])
    return out


def test_preempted_and_restored_decodes_identically():
    """Pausing a request through the host tier and restoring its latent
    frames gives the same tokens and bytes as never pausing."""
    params = _params(seed=4)
    rng = np.random.default_rng(6)
    prompt = rng.integers(0, TINY.vocab_size, 19).tolist()
    runs = []
    for pause in (False, True):
        eng = InstanceEngine(params, TINY, max_batch=2, max_local_len=64,
                             pool_blocks=16, block_size=8, prefill_chunk=8)
        req = Request(prompt=prompt, sampling=SamplingParams(
            max_new_tokens=12))
        eng.submit(req)
        eng.step()
        _decode(eng, req, 3)
        if pause:
            n_tokens, frames = eng.read_chain_frames(req)
            assert all(v is None and k.shape[-1] == TINY.latent_pool_dim
                       for k, v in frames)
            tier = HostKVTier(8, verify=True)
            for i, (k, v) in enumerate(frames):
                assert tier.put(i, k, v)
            tier.drain(block=True)
            eng.finalize_pause(req)
            assert eng.rmanager.pool.alloc.used_count == 0
            assert eng.resume_paused(req, n_tokens,
                                     [tier.get(i) for i in range(len(frames))])
        _decode(eng, req, 6)
        blocks = eng.rmanager.pool.requests[req.req_id].blocks
        rows = np.asarray(eng.pool_k[:, blocks])
        runs.append((list(req.output), rows))
    assert runs[0][0] == runs[1][0]
    np.testing.assert_array_equal(runs[0][1], runs[1][1])


def test_prefix_cache_hit_decodes_like_a_cold_run():
    """A repeated prompt takes its latent blocks from the prefix cache
    and decodes the same tokens as the first, cold request."""
    params = _params(seed=5)
    rng = np.random.default_rng(7)
    prompt = rng.integers(0, TINY.vocab_size, 20).tolist()
    cl = Cluster(params, TINY, ServingConfig.smoke(n_instances=1,
                                                   prefix_cache=True))
    outs = []
    for _ in range(2):
        req = Request(prompt=prompt, sampling=SamplingParams(
            max_new_tokens=6))
        cl.submit(req)
        cl.run_until_done(max_steps=50)
        outs.append(req.output)
    assert cl.engines[0].stats.cache_hit_tokens > 0
    assert outs[0] == outs[1]


def test_latent_family_refuses_the_global_pool():
    with pytest.raises(ValueError, match="per-instance"):
        Cluster(_params(), TINY, ServingConfig.smoke(global_pool=True))


# ------------------------------------------------------------------ #
# The paged latent kernel against its jnp form
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("nblk,tail,mb", [
    ((3, 0, 1, 2), (5, 16, 16, 1), 8),       # table padding, empty row
    ((40, 33, 17, 64), (7, 16, 2, 16), 64),  # many blocks, many tiles
    ((1, 1, 1, 1), (1, 9, 16, 4), 8),        # a one-block context
], ids=["padding", "many-tiles", "one-block"])
def test_latent_kernel_matches_jnp(nblk, tail, mb, dtype, monkeypatch):
    # Small tiles so that the many-block case runs several of them.
    monkeypatch.setattr(ops, "_LATENT_TILE_BYTES", 4 * 16 * 128 * 4)
    L, NB, bs, W, r, H = 3, 96, 16, 128, 32, 8
    pool = jax.random.normal(jax.random.PRNGKey(0), (L, NB, bs, W),
                             jnp.float32).astype(dtype)
    q = jax.random.normal(jax.random.PRNGKey(1), (len(nblk), H, W),
                          jnp.float32).astype(dtype)
    perm = np.random.default_rng(0).permutation(NB)
    table = -np.ones((len(nblk), mb), np.int32)
    for i, n in enumerate(nblk):
        table[i, :n] = perm[i * 24:i * 24 + n] if n <= 24 else perm[:n]
    tails = np.asarray(tail, np.int32)
    for layer in (0, L - 1):
        want = ops.paged_latent_attention(q, pool, layer, table, tails,
                                          rank=r, scale=0.1, backend="jnp")
        got = ops.paged_latent_attention(q, pool, layer, table, tails,
                                         rank=r, scale=0.1,
                                         backend="pallas", interpret=True)
        empty = np.asarray(nblk) == 0
        assert np.isneginf(np.asarray(got[1])[empty]).all()
        assert (np.asarray(got[2])[empty] == 0).all()
        if dtype == "float32":
            # Same arithmetic; only the order of the sums over tiles
            # differs.
            for g, w in zip(got, want):
                np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                           rtol=1e-5, atol=1e-5)
            continue
        # In bfloat16 both round p to the pool's dtype for the value
        # product, the kernel relative to each tile's running maximum
        # and the jnp form to the row's: the normalised outputs (values
        # of order 0.1-0.5) then differ by bfloat16 steps of p (2^-8 of
        # each weight), read at most 4.7e-4; compared at 5e-3.
        ok = ~empty
        norm = [np.asarray(o)[ok] / np.asarray(l)[ok][..., None]
                for o, _, l in (got, want)]
        np.testing.assert_allclose(norm[0], norm[1], atol=5e-3, rtol=0)
        np.testing.assert_allclose(np.asarray(got[1])[ok],
                                   np.asarray(want[1])[ok], rtol=1e-5)


# ------------------------------------------------------------------ #
# YaRN at the published sizes
# ------------------------------------------------------------------ #
def test_yarn_at_deepseek_v2_lite():
    cfg = ModelConfig(name="v2-lite-attn", family="mla",
                      qk_nope_head_dim=128, qk_rope_head_dim=64,
                      kv_lora_rank=512, rope_factor=40.0,
                      rope_original_max_pos=4096, rope_mscale=0.707,
                      rope_mscale_all_dim=0.707)
    m = 0.1 * 0.707 * math.log(40.0) + 1.0
    assert mla.softmax_scale(cfg) == pytest.approx(192 ** -0.5 * m * m)
    inv = mla.rope_inv_freq(cfg)
    plain = 1.0 / 10_000.0 ** (np.arange(0, 64, 2) / 64)
    # dims that turn more than beta_fast times in 4,096 tokens keep
    # their frequency; those under beta_slow turns are divided by 40.
    np.testing.assert_allclose(inv[:11], plain[:11], rtol=1e-6)
    np.testing.assert_allclose(inv[23:], plain[23:] / 40.0, rtol=1e-6)
    assert (inv[11:23] < plain[11:23]).all()
    assert (inv[11:23] > plain[11:23] / 40.0).all()
    assert cfg.latent_dim == 576 and cfg.latent_pool_dim == 640
