"""Operations and bytes the algorithm needs, from the model's shapes.

Counted from the work actually asked for (tokens prefilled, tokens
decoded, context lengths attended), never from the padding, the table
buckets or the grid the kernels happen to run; so the same work counts
the same whatever implements it. One multiply-add is two operations.
"""
from __future__ import annotations

from harness.spec import ModelSpec

BF16 = 2
F32 = 4


def layer_params(m: ModelSpec) -> int:
    """Weights of one transformer layer (matrices only)."""
    d, H, K, hd, F = m.d_model, m.heads, m.kv_heads, m.head_dim, m.d_ff
    return d * H * hd * 2 + d * K * hd * 2 + 3 * d * F


def param_count(m: ModelSpec) -> int:
    """All weights: layers, embedding and (untied) head."""
    emb = m.vocab * m.d_model
    return m.layers * layer_params(m) + emb * (1 if m.tied else 2)


def kv_bytes_per_token(m: ModelSpec, itemsize: int = BF16) -> int:
    """K and V of one token over every layer."""
    return 2 * m.layers * m.kv_heads * m.head_dim * itemsize


def token_flops(m: ModelSpec, context: int, logits: bool) -> float:
    """Forward operations of one token that attends ``context`` tokens
    (itself included); ``logits`` adds the output head."""
    f = 2.0 * m.layers * layer_params(m)
    f += 4.0 * m.layers * context * m.heads * m.head_dim
    if logits:
        f += 2.0 * m.d_model * m.vocab
    return f


def prompt_flops(m: ModelSpec, n_prompt: int) -> float:
    """A whole prompt prefilled: causal attention, one set of logits."""
    f = 2.0 * m.layers * layer_params(m) * n_prompt
    f += 4.0 * m.layers * m.heads * m.head_dim * n_prompt * (n_prompt + 1) / 2
    return f + 2.0 * m.d_model * m.vocab


def decode_attn_work(m: ModelSpec, context: int, spans: int = 1):
    """(operations, bytes) of the paged decode attention of one token
    over ``context`` tokens held in ``spans`` pools, all layers: read K
    and V of the context once, q in, (o, m, l) out per span."""
    H, K, hd, L = m.heads, m.kv_heads, m.head_dim, m.layers
    flops = 4.0 * L * context * H * hd
    nbytes = L * (2 * context * K * hd * BF16
                  + spans * (H * hd * BF16 + H * hd * F32 + 2 * H * F32))
    return flops, nbytes


def prefill_attn_work(m: ModelSpec, n_query: int, prefix: int,
                      spans: int = 1):
    """(operations, bytes) of the paged prefill attention of one chunk:
    ``n_query`` chunk tokens over ``prefix`` already written tokens in
    ``spans`` pools, all layers. The chunk's causal part is not the
    kernel's work and is not counted."""
    H, K, hd, L = m.heads, m.kv_heads, m.head_dim, m.layers
    flops = 4.0 * L * n_query * prefix * H * hd
    nbytes = L * (2 * prefix * K * hd * BF16
                  + spans * n_query * (H * hd * BF16 + H * hd * F32
                                       + 2 * H * F32))
    return flops, nbytes
