"""An expert layer that holds a share of the routed experts.

An instance of a deployment that spreads the experts over chips routes
each token over ALL of them and computes only the ones it holds
(``ModelConfig.held_experts``); the shares of all instances add up to
the whole layer. The router may keep the raw softmax gates
(``norm_topk_prob=False``, DeepSeek-V2). The defaults leave the
existing expert layers unchanged, bit for bit: the digests below were
read from ``apply_moe`` and ``forward`` before the expert share existed.
"""
import dataclasses
import hashlib
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.configs.base import ModelConfig
from repro.models.model import forward, init_params
from repro.models.moe import apply_moe, route

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "bench")

SMALL = ModelConfig(name="share", family="moe", num_layers=2, d_model=32,
                    num_heads=2, num_kv_heads=2, head_dim=16, d_ff=64,
                    vocab_size=64, num_experts=8, num_shared_experts=2,
                    top_k=3, moe_d_ff=24, first_k_dense=0, dtype="float32")


def _layer(cfg):
    params = init_params(jax.random.PRNGKey(0), cfg)
    return jax.tree.map(lambda a: a[0], params["moe_layers"])["moe"]


@pytest.mark.parametrize("norm", [True, False], ids=["renorm", "raw"])
def test_shares_sum_to_the_whole_layer(norm):
    cfg = dataclasses.replace(SMALL, norm_topk_prob=norm)
    whole = _layer(cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 5, cfg.d_model))
    want, held_all = apply_moe(whole, x, cfg, capacity_factor=-1.0,
                               with_held=True)
    assert (np.asarray(held_all) == cfg.top_k).all()
    total, held = 0.0, 0
    for lo in range(0, cfg.num_experts, 2):      # four shares of two
        share = dataclasses.replace(cfg, held_experts=(lo, 2))
        p = dict(whole, experts=jax.tree.map(lambda a: a[lo:lo + 2],
                                             whole["experts"]))
        y, h = apply_moe(p, x, share, capacity_factor=-1.0, with_held=True)
        total = total + y
        held = held + np.asarray(h)
    # Every share runs the shared experts; count them once.
    xs = x.reshape(-1, cfg.d_model)
    shared = 0.0
    for e in range(cfg.num_shared_experts):
        w = jax.tree.map(lambda a: a[e], whole["shared"])
        shared = shared + (jax.nn.silu(xs @ w["wi"]) * (xs @ w["wg"])) \
            @ w["wo"]
    total = total - 3 * shared.reshape(x.shape)
    # float32 on both sides; the shares only sum in another order.
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=1e-5, rtol=1e-5)
    assert (held == cfg.top_k).all()


def test_raw_gates_are_the_softmax_probabilities():
    cfg = dataclasses.replace(SMALL, norm_topk_prob=False)
    p = _layer(cfg)
    xt = jax.random.normal(jax.random.PRNGKey(2), (7, cfg.d_model))
    gates, idx = route(p, xt, cfg)
    probs = jax.nn.softmax(xt @ p["router"], -1)
    np.testing.assert_array_equal(
        np.asarray(gates),
        np.take_along_axis(np.asarray(probs), np.asarray(idx), -1))
    assert (np.asarray(gates).sum(-1) < 1.0).all()
    renorm, _ = route(p, xt, SMALL)
    np.testing.assert_allclose(np.asarray(renorm).sum(-1), 1.0, rtol=1e-6)


def test_unheld_experts_are_neither_stored_nor_run():
    cfg = dataclasses.replace(SMALL, held_experts=(6, 2),
                              norm_topk_prob=False)
    p = _layer(cfg)
    assert p["router"].shape == (cfg.d_model, 8)
    assert p["experts"]["wi"].shape[0] == 2
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 6, cfg.d_model))
    _, held = apply_moe(p, x, cfg, capacity_factor=-1.0, with_held=True)
    _, idx = route(p, x.reshape(-1, cfg.d_model), cfg)
    want = (np.asarray(idx) >= 6).sum(-1).reshape(2, 6)
    np.testing.assert_array_equal(np.asarray(held), want)


def _digest(a):
    return hashlib.sha256(np.asarray(a).tobytes()).hexdigest()[:16]


def test_existing_expert_layers_are_unchanged():
    cfg = get_smoke_config("qwen2-moe-a2.7b")
    params = init_params(jax.random.PRNGKey(0), cfg)
    lp = jax.tree.map(lambda a: a[0], params["moe_layers"])["moe"]
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, cfg.d_model),
                          jnp.float32).astype(cfg.dtype)
    toks = jax.random.randint(jax.random.PRNGKey(2), (2, 16), 0,
                              cfg.vocab_size)
    got = {
        "qwen_moe_nodrop": apply_moe(lp, x, cfg, capacity_factor=-1.0),
        "qwen_moe_cap": apply_moe(lp, x, cfg, capacity_factor=1.25),
        "qwen_forward": forward(params, cfg, toks)[0],
    }
    # The harness's toy MoE (bench/tests/data/archs/toy_moe.py).
    sys.path[:0] = [BENCH]
    try:
        from harness.spec import load_arch
        from harness.weights import root_key
        toy = load_arch("toy_moe", os.path.join(BENCH, "tests", "data",
                                                "archs"))
    finally:
        sys.path.remove(BENCH)
    m = toy.spec({"name": "tiny", "num_hidden_layers": 3,
                  "hidden_size": 64, "num_attention_heads": 4,
                  "num_key_value_heads": 2, "head_dim": 16,
                  "intermediate_size": 128, "vocab_size": 256,
                  "rms_norm_eps": 1e-5, "rope_theta": 10000.0,
                  "tie_word_embeddings": False, "serve_dtype": "bfloat16",
                  "first_k_dense_replace": 1, "moe_intermediate_size": 32,
                  "n_routed_experts": 8, "n_shared_experts": 1,
                  "num_experts_per_tok": 2})
    tcfg = toy.program_config(m)
    tp = toy.program_params(root_key(2**33 + 5), m)
    got["toy_forward"] = forward(tp, tcfg, toks)[0]
    tl = jax.tree.map(lambda a: a[0], tp["moe_layers"])["moe"]
    xt = jax.random.normal(jax.random.PRNGKey(3), (1, 8, 64),
                           jnp.float32).astype(jnp.bfloat16)
    got["toy_moe_nodrop"] = apply_moe(tl, xt, tcfg, capacity_factor=-1.0)
    want = {"qwen_moe_nodrop": "d49a7eca0a4566c2",
            "qwen_moe_cap": "b708ad670cba5875",
            "qwen_forward": "3f8f6c07c5608605",
            "toy_forward": "045eccf6a00c4d40",
            "toy_moe_nodrop": "3759630094634528"}
    assert {k: _digest(v) for k, v in got.items()} == want
