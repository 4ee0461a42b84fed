"""The comparison that decides ``correct``, on the CPU at a tiny size:
the weights the server gets are the ones the reference draws again; a
sound run is correct; a run with the timed path broken underneath is
not, for each fault a serving cell can have. The same for a small
mixture-of-experts architecture that the harness loads from
``tests/data/archs`` (new files only, no harness edit)."""
import hashlib
import json
import os
import time

import jax
import numpy as np
import pytest

from harness import reference
from harness.runner import run_cell
from harness.spec import Cell, load_arch, load_benchmark
from harness.weights import layer_key, root_key

SEED = 2**33 + 5
dense = load_arch("dense")
TEST_ARCHS = os.path.join(os.path.dirname(__file__), "data", "archs")
# The program routes on bfloat16 router logits, the reference on float32:
# where two gates nearly tie, a token's expert flips, and a sound run's
# widest gap reads 0.011-0.359 over seeds 11-18 and SEED, against the
# float8 control's 1.12-1.42 (seeds 11, 16-18, SEED; a top-8-of-8 router,
# which selects nothing, reads 0.021-0.058). Hence its own limit.
TOY_MOE = {"arch": "toy_moe", "num_hidden_layers": 3,
           "first_k_dense_replace": 1, "moe_intermediate_size": 32,
           "n_routed_experts": 8, "n_shared_experts": 1,
           "num_experts_per_tok": 2, "check": {"max_logit_gap": 0.6}}


SESSIONS = {"kind": "sessions", "order_seed": 3, "sessions": 2,
            "prompt": {"dist": "uniform", "lo": 80, "hi": 120},
            "output": {"dist": "fixed", "value": 100}, "decode_warmup": 4,
            "shape_warmup": [[100, 4]],
            "check": {"requests": 2, "finished": False, "pad_to": 2048}}
ON_MESH = {"global_pool": True, "overload": {"enabled": False},
           "mesh": {"shape": [1, 1], "axes": ["data", "model"],
                    "batch_axes": ["data"], "pool_axes": ["data"]}}


def tiny_cell(norm="rmsnorm", tied=False, mix=None, deployment=None,
              arch=None) -> Cell:
    cfg = {
        "name": "tiny", "num_hidden_layers": 2, "hidden_size": 64,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "intermediate_size": 128, "vocab_size": 256, "norm": norm,
        "rms_norm_eps": 1e-5, "rope_theta": 10000.0,
        "tie_word_embeddings": tied, "serve_dtype": "bfloat16",
        "deployment": {"serving": {
            "n_instances": 2, "max_batch": 4, "block_size": 8,
            "max_local_len": 64, "prefill_chunk": 16, "pool_blocks": 96,
            "move_chunk_tokens": 8, "schedule_every": 4,
            "heartbeat_timeout": 1e9, "avg_new_req_len": 16,
            "max_waiting": 64}},
        "check": {"max_logit_gap": 0.1},
        **(arch or {}),
    }
    dep = dict(deployment or {})
    if "mesh" in dep:
        cfg["deployment"]["mesh"] = dep.pop("mesh")
    cfg["deployment"]["serving"].update(dep)
    mix_open = {"kind": "open", "order_seed": 3, "warmup_s": 1.0, "streams": [{
        "arrivals": {"process": "poisson", "rate_hz": 6.0},
        "prompt": {"dist": "lognormal", "median": 24, "sigma": 0.8,
                   "lo": 8, "hi": 100},
        "output": {"dist": "lognormal", "median": 12, "sigma": 0.5,
                   "lo": 4, "hi": 24}}],
        "shape_warmup": [[100, 4]],
        "check": {"requests": 4, "finished": True, "pad_to": 2048}}
    mix = mix or mix_open
    b = load_benchmark()
    return Cell("tiny", 1, cfg, "tiny", mix, b["end_to_end"], b["per_layer"],
                **({"arch_dir": TEST_ARCHS} if arch else {}))


def run(cell, control=False):
    return run_cell(cell, SEED, 3.0, False, time.monotonic(),
                    require_tpu=False, control=control)


def test_program_params_are_the_reference_layers():
    m = tiny_cell().model
    key = root_key(SEED)
    p = dense.program_params(key, m)
    for layer in range(m.layers):
        w = dense.layer_weights(layer_key(key, layer), m)
        got = {"wq": p["layers"]["attn"]["wq"], "wo": p["layers"]["attn"]["wo"],
               "w_gate": p["layers"]["ffn"]["wi"],
               "w_up": p["layers"]["ffn"]["wg"],
               "w_down": p["layers"]["ffn"]["wo"],
               "attn_norm": p["layers"]["ln1"]["scale"]}
        for name, arr in got.items():
            np.testing.assert_array_equal(np.asarray(arr[layer]),
                                          np.asarray(w[name]))


@pytest.mark.parametrize("norm,tied", [("rmsnorm", False),
                                       ("layernorm", True)])
def test_dense_matches_numbers_recorded_before_the_split(norm, tied):
    """The dense architecture module gives bit for bit the weights,
    reference gaps and counts that the harness gave before it held the
    dense model itself (``tests/data/dense_golden.json``, recorded at
    this size on the CPU)."""
    with open(os.path.join(os.path.dirname(__file__), "data",
                           "dense_golden.json")) as f:
        want = json.load(f)[norm]
    m = tiny_cell(norm, tied).model
    p = dense.program_params(root_key(SEED), m)
    got = {jax.tree_util.keystr(k): np.asarray(v)
           for k, v in jax.tree_util.tree_leaves_with_path(p)}
    assert set(got) == set(want["weights"])
    for name, (digest, total) in want["weights"].items():
        assert hashlib.sha256(got[name].tobytes()).hexdigest()[:16] == \
            digest, name
        assert float(got[name].astype(np.float64).sum()) == total, name
    rng = np.random.default_rng(7)
    seqs = [(rng.integers(0, 256, a).tolist(),
             rng.integers(0, 256, b).tolist())
            for a, b in ((30, 10), (45, 6), (3, 1))]
    g = dense.gaps(m, SEED, seqs, 2048, control=True)
    assert [float(x) for x in g["served"]] == want["served"]
    assert [float(x) for x in g["control"]] == want["control"]
    assert [dense.param_count(m), dense.kv_bytes_per_token(m),
            dense.token_flops(m, 37, True), dense.prompt_flops(m, 11),
            list(dense.decode_attn_work(m, 37, 2)),
            list(dense.prefill_attn_work(m, 16, 32, 2))] == want["counts"]


def test_fp8_round_is_coarser_than_bf16():
    w = jax.random.normal(jax.random.PRNGKey(0), (64, 32))
    e8 = float(np.abs(reference.fp8_round(w) - w).max())
    e16 = float(np.abs(w.astype(jax.numpy.bfloat16).astype(float) - w).max())
    assert e8 > 4 * e16


@pytest.mark.parametrize("norm,tied,mix,deployment", [
    ("rmsnorm", False, None, None),
    ("layernorm", True, None, None),
    ("rmsnorm", False, SESSIONS, None),
    ("rmsnorm", False, None, ON_MESH)],
    ids=["rmsnorm-False", "layernorm-True", "sessions-spanning",
         "global-pool-on-mesh"])
def test_sound_run_is_correct(norm, tied, mix, deployment):
    res = run(tiny_cell(norm, tied, mix, deployment))
    assert res["correct"], res["compared"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["metrics"]["output_tok_s"]["value"] > 0
    assert list(res)[-1] == "compared"


def test_control_is_not_correct():
    """The float8 control, judged by the same numbers and limits in the
    program's place, fails where the program passes."""
    res = run(tiny_cell(), control=True)
    assert res["correct"], res["compared"]
    assert not res["control"]["correct"], res["control"]
    assert list(res)[-1] == "compared"


def test_deployment_builds_the_program_it_names():
    from harness.runner import program_config, program_mesh
    cell = tiny_cell(deployment=ON_MESH)
    _, sc = program_config(cell)
    assert sc.global_pool and type(sc.overload).__name__ == "OverloadPolicy"
    assert not sc.overload.enabled
    mesh, layout = program_mesh(cell, jax.devices())
    assert dict(mesh.shape) == {"data": 1, "model": 1}
    assert layout.pool_axes == ("data",)
    assert program_mesh(tiny_cell(), jax.devices()) == (None, None)


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
    orig = engine.decode_step_paged

    def broken(params, cfg, tokens, lens, pool_k, pool_v, tables, tails,
               write_block, write_off, remote_pools=(), **kw):
        nowhere = np.full_like(write_block, pool_k.shape[1])
        return orig(params, cfg, tokens, lens, pool_k, pool_v, tables,
                    tails, nowhere, write_off, remote_pools=remote_pools,
                    **kw)
    monkeypatch.setattr(engine, "decode_step_paged", broken)


def _no_exchange(monkeypatch):
    from repro.serving import engine
    orig = engine.decode_step_paged

    def broken(params, cfg, tokens, lens, pool_k, pool_v, tables, tails,
               write_block, write_off, remote_pools=(), **kw):
        return orig(params, cfg, tokens, lens, pool_k, pool_v, tables[:1],
                    tails[:1], write_block, write_off, remote_pools=(), **kw)
    monkeypatch.setattr(engine, "decode_step_paged", broken)


@pytest.mark.parametrize("fault", [_alter_token, _state_unchanged,
                                   _no_exchange],
                         ids=["token_altered", "state_unchanged",
                              "exchange_left_out"])
def test_broken_run_is_not_correct(fault, monkeypatch):
    fault(monkeypatch)
    res = run(tiny_cell())
    assert not res["correct"], res["compared"]


def test_toy_moe_params_are_the_reference_layers():
    cell = tiny_cell(arch=TOY_MOE)
    toy, m = cell.arch, cell.model
    assert cell.arch is not dense and type(m).__module__ == toy.__name__
    key = root_key(SEED)
    p = toy.program_params(key, m)
    for layer in range(m.layers):
        moe = layer >= m.dense_layers
        w = toy.layer_weights(layer_key(key, layer), m, moe)
        stack = p["moe_layers" if moe else "dense_layers"]
        i = layer - m.dense_layers if moe else layer
        got = {"wq": stack["attn"]["wq"], "wv": stack["attn"]["wv"],
               "attn_norm": stack["ln1"]["scale"]}
        if moe:
            got.update(router=stack["moe"]["router"],
                       e_gate=stack["moe"]["experts"]["wi"],
                       e_down=stack["moe"]["experts"]["wo"],
                       s_up=stack["moe"]["shared"]["wg"])
        else:
            got.update(w_gate=stack["ffn"]["wi"], w_down=stack["ffn"]["wo"])
        for name, arr in got.items():
            np.testing.assert_array_equal(np.asarray(arr[i]),
                                          np.asarray(w[name]))


def test_toy_moe_sound_run_is_correct_and_control_is_not():
    res = run(tiny_cell(mix=SESSIONS, arch=TOY_MOE), control=True)
    assert res["correct"], res["compared"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert not res["control"]["correct"], res["control"]
    assert res["metrics"]["output_tok_s"]["value"] > 0


@pytest.mark.parametrize("fault", [_alter_token, _state_unchanged,
                                   _no_exchange],
                         ids=["token_altered", "state_unchanged",
                              "exchange_left_out"])
def test_toy_moe_broken_run_is_not_correct(fault, monkeypatch):
    fault(monkeypatch)
    res = run(tiny_cell(mix=SESSIONS, arch=TOY_MOE))
    assert not res["correct"], res["compared"]
