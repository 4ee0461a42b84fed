"""Operation and byte counts of the dense architecture against
hand-worked values."""
import pytest

from harness.spec import load_arch, load_cell

counts = load_arch("dense")

NEMO = load_cell("nemo12b-longdecode").model
OLMO = load_cell("olmo1b-chat").model


def test_nemo_8l_sizes():
    # q 5120x4096 + k, v 5120x1024 each + o 4096x5120 + 3 x 5120x14336
    assert counts.layer_params(NEMO) == 272_629_760
    # 8 layers + untied embedding and head of 131072 x 5120 each
    assert counts.param_count(NEMO) == 8 * 272_629_760 + 2 * 671_088_640
    assert counts.param_count(NEMO) / 1e9 == pytest.approx(3.52, abs=0.005)
    # K and V, 8 layers x 8 heads x 128 x 2 bytes each: 32 KiB
    assert counts.kv_bytes_per_token(NEMO) == 32 * 1024


def test_olmo_1b_sizes():
    assert counts.layer_params(OLMO) == 4 * 2048 * 2048 + 3 * 2048 * 8192
    assert counts.param_count(OLMO) / 1e9 == pytest.approx(1.18, abs=0.005)
    assert counts.kv_bytes_per_token(OLMO) == 128 * 1024


def test_token_and_prompt_flops():
    ctx = 1000
    f = counts.token_flops(OLMO, ctx, logits=True)
    want = (2 * 16 * counts.layer_params(OLMO) + 4 * 16 * ctx * 16 * 128
            + 2 * 2048 * 50304)
    assert f == want
    # A prompt is the sum of its tokens' causal attention, one head.
    T = 7
    p = counts.prompt_flops(OLMO, T)
    per_tok = sum(counts.token_flops(OLMO, t + 1, False) for t in range(T))
    assert p == pytest.approx(per_tok + 2 * 2048 * 50304)


def test_decode_attention_work():
    f, b = counts.decode_attn_work(NEMO, context=16_000, spans=2)
    assert f == 4 * 8 * 16_000 * 32 * 128
    # KV of 16,000 tokens (32 KiB each) + q, o, m, l per layer and span
    per_span = 32 * 128 * 2 + 32 * 128 * 4 + 2 * 32 * 4
    assert b == 16_000 * 32 * 1024 + 8 * 2 * per_span


def test_prefill_attention_work():
    f, b = counts.prefill_attn_work(OLMO, n_query=256, prefix=512)
    assert f == 4 * 16 * 256 * 512 * 16 * 128
    assert b == 512 * 128 * 1024 + 16 * 256 * (16 * 128 * 6 + 2 * 16 * 4)
    assert counts.prefill_attn_work(OLMO, 256, 0)[0] == 0
