"""The latent-attention expert architecture (``archs/mla_moe.py``): its
counts at DeepSeek-V2-Lite's published widths against hand-worked
values, its weights against the program's, and the comparison that
decides ``correct`` on the CPU at a tiny size: a sound run through
``run_cell`` is correct, the float8 control and every fault of the timed
path are not."""
import time

import numpy as np
import pytest

from harness.runner import run_cell
from harness.spec import Cell, load_arch, load_benchmark, load_cell
from harness.weights import layer_key, root_key

mla = load_arch("mla_moe")
V2 = load_cell("dsv2lite-longdecode").model
SEED = 2**33 + 5

# A tiny DeepSeek-V2: a leading dense layer, two expert layers holding 4
# of 8 routed experts (top 2, raw gates), YaRN over an original context
# of 32 tokens, shorter than every prompt. The program routes on
# bfloat16 router logits, the reference on float32, so a near-tie flips
# an expert: a sound run's widest gap read 0.009-0.363 over seeds 11-18
# and 0.117 at SEED, the float8 control's 0.783-1.429 (0.873 at SEED;
# 0.689 there with an empty window, the warm-up's tokens alone). Hence
# its own limit, between the two on every seed read.
TINY = {
    "name": "tiny-mla", "arch": "mla_moe", "num_hidden_layers": 3,
    "first_k_dense_replace": 1, "hidden_size": 64,
    "num_attention_heads": 4, "q_lora_rank": None, "kv_lora_rank": 32,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "intermediate_size": 96, "moe_intermediate_size": 32,
    "n_routed_experts": 4, "n_shared_experts": 1, "num_experts_per_tok": 2,
    "norm_topk_prob": False, "routed_scaling_factor": 1, "vocab_size": 256,
    "rms_norm_eps": 1e-6, "rope_theta": 10000,
    "rope_scaling": {"type": "yarn", "factor": 4,
                     "original_max_position_embeddings": 32,
                     "beta_fast": 32, "beta_slow": 1, "mscale": 0.707,
                     "mscale_all_dim": 0.707},
    "tie_word_embeddings": False, "serve_dtype": "bfloat16",
    "published": {"n_routed_experts": 8},
    "deployment": {"serving": {
        "n_instances": 2, "max_batch": 4, "block_size": 8,
        "max_local_len": 64, "prefill_chunk": 16, "pool_blocks": 96,
        "move_chunk_tokens": 8, "schedule_every": 4,
        "heartbeat_timeout": 1e9, "avg_new_req_len": 16, "max_waiting": 64}},
    "check": {"max_logit_gap": 0.5},
}
# Two sessions longer than the local quota: each spans the other
# instance, so the creditor partials and their merge are on the path.
# 40 tokens of decode warm-up: the sample holds at least that many
# served tokens a request however slowly the CPU runs the window (with 4
# and an empty window the control's widest gap read 0.08 at SEED).
SESSIONS = {"kind": "sessions", "order_seed": 3, "sessions": 2,
            "prompt": {"dist": "uniform", "lo": 80, "hi": 120},
            "output": {"dist": "fixed", "value": 140}, "decode_warmup": 40,
            "shape_warmup": [[100, 4]],
            "check": {"requests": 2, "finished": False, "pad_to": 2048}}


def tiny_cell() -> Cell:
    b = load_benchmark()
    return Cell("tiny-mla", 1, TINY, "tiny", SESSIONS, b["end_to_end"],
                b["per_layer"])


def run(cell, control=False):
    return run_cell(cell, SEED, 3.0, False, time.monotonic(),
                    require_tpu=False, control=control)


# --- counts at the published widths --------------------------------- #
def test_v2_lite_cut_sizes():
    assert (V2.layers, V2.experts, V2.router, V2.vocab) == (5, 8, 64, 12800)
    # q 2048x16x192, kv_a 2048x576, kv_b 512x16x256, o 16x128x2048
    assert mla.attn_params(V2) == 13_762_560
    # layer 0: attention + a 10,944-wide SwiGLU; 4 expert layers:
    # attention, router 2048x64, 8 held + 2 shared experts of 1,408;
    # embedding and head 12,800 x 2,048 each.
    layer0 = 13_762_560 + 3 * 2048 * 10944
    moe = 13_762_560 + 2048 * 64 + 10 * 3 * 2048 * 1408
    assert (layer0, moe) == (81_002_496, 100_401_152)
    assert mla.param_count(V2) == layer0 + 4 * moe + 2 * 12800 * 2048
    assert mla.param_count(V2) == 535_035_904
    # One 576-wide bf16 row per layer.
    assert mla.kv_bytes_per_token(V2) == 5 * 576 * 2 == 5760


def test_v2_lite_decode_attention_work():
    f, b = mla.decode_attn_work(V2, context=100_000, spans=2)
    # 16 heads score a 576-wide row and sum its 512 leading entries.
    assert f == 5 * 2 * 16 * (576 + 512) * 100_000
    # rows once, and per layer and span the absorbed query in (bf16)
    # and the latent partial out (o 16x512 f32, m and l 16 f32 each).
    per_span = 16 * 576 * 2 + 16 * 512 * 4 + 2 * 16 * 4
    assert b == 5 * 1152 * 100_000 + 5 * 2 * per_span
    assert f / b == pytest.approx(30.2, abs=0.05)


def test_v2_lite_token_and_prompt_flops():
    # A decode token's matrices: attention absorbed, the dense FFN once,
    # per expert layer the router, 6 x 8/64 held routes and 2 shared.
    attn = (2048 * 16 * 192 + 2048 * 576 + 2 * 16 * 128 * 512
            + 16 * 128 * 2048)
    moe = 2048 * 64 + 3 * 2048 * 1408 * (0.75 + 2)
    weights = 2 * (5 * attn + 3 * 2048 * 10944 + 4 * moe)
    ctx = 1000
    assert mla.token_flops(V2, ctx, logits=False) == pytest.approx(
        weights + 5 * 2 * 16 * 1088 * ctx)
    T = 7
    per_tok = sum(mla.token_flops(V2, t + 1, False) for t in range(T))
    assert mla.prompt_flops(V2, T) == pytest.approx(
        per_tok + 2 * 2048 * 12800)
    f, _ = mla.prefill_attn_work(V2, n_query=256, prefix=512)
    assert f == 5 * 2 * 16 * 1088 * 256 * 512


# --- the program's weights are the reference's --------------------- #
def test_program_params_are_the_reference_layers():
    cell = tiny_cell()
    m = cell.model
    key = root_key(SEED)
    p = mla.program_params(key, m)
    for layer in range(m.layers):
        moe = layer >= m.dense_layers
        w = mla.layer_weights(layer_key(key, layer), m, moe)
        stack = p["moe_layers" if moe else "dense_layers"]
        i = layer - m.dense_layers if moe else layer
        got = {"wq": stack["attn"]["wq"], "wkv_b": stack["attn"]["wkv_b"],
               "kv_norm": stack["attn"]["kv_norm"]["scale"],
               "attn_norm": stack["ln1"]["scale"]}
        if moe:
            got.update(router=stack["moe"]["router"],
                       e_gate=stack["moe"]["experts"]["wi"],
                       s_down=stack["moe"]["shared"]["wo"])
        else:
            got.update(w_up=stack["ffn"]["wg"])
        for name, arr in got.items():
            np.testing.assert_array_equal(np.asarray(arr[i]),
                                          np.asarray(w[name]))
    cfg = mla.program_config(m)
    assert cfg.num_experts == 8 and cfg.expert_range == (0, 4)
    assert not cfg.norm_topk_prob and cfg.latent_dim == 40


# --- correct, and not correct ---------------------------------------- #
def test_sound_run_is_correct_and_control_is_not():
    res = run(tiny_cell(), control=True)
    assert res["correct"], res["compared"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert not res["control"]["correct"], res["control"]
    assert res["metrics"]["output_tok_s"]["value"] > 0


def _alter_token(monkeypatch):
    from repro.serving.engine import InstanceEngine
    orig = InstanceEngine._sample_tokens

    def broken(self, logits, reqs):
        toks = np.array(orig(self, logits, reqs))
        toks[0] = (toks[0] + 1) % self.cfg.vocab_size
        return toks
    monkeypatch.setattr(InstanceEngine, "_sample_tokens", broken)


def _state_unchanged(monkeypatch):
    from repro.serving import engine
    orig = engine.decode_step_latent

    def broken(params, cfg, tokens, lens, pool, tables, tails, write_block,
               write_off, remote_pools=(), **kw):
        nowhere = np.full_like(write_block, pool.shape[1])
        return orig(params, cfg, tokens, lens, pool, tables, tails, nowhere,
                    write_off, remote_pools=remote_pools, **kw)
    monkeypatch.setattr(engine, "decode_step_latent", broken)


def _no_exchange(monkeypatch):
    from repro.serving import engine
    orig = engine.decode_step_latent

    def broken(params, cfg, tokens, lens, pool, tables, tails, write_block,
               write_off, remote_pools=(), **kw):
        return orig(params, cfg, tokens, lens, pool, tables[:1], tails[:1],
                    write_block, write_off, remote_pools=(), **kw)
    monkeypatch.setattr(engine, "decode_step_latent", broken)


@pytest.mark.parametrize("fault", [_alter_token, _state_unchanged,
                                   _no_exchange],
                         ids=["token_altered", "state_unchanged",
                              "exchange_left_out"])
def test_broken_run_is_not_correct(fault, monkeypatch):
    fault(monkeypatch)
    res = run(tiny_cell())
    assert not res["correct"], res["compared"]
