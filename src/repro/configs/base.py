"""Config dataclasses for models, shapes, and the serving/training runtime.

Every assigned architecture is expressed as a ``ModelConfig``; the four
assigned input shapes are ``ShapeConfig`` instances in ``SHAPES``.  Reduced
("smoke") variants for CPU tests are derived with ``reduce_config`` so they
preserve the structural family (MoE routing, hybrid layer pattern, sLSTM
placement) while shrinking every dimension.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Optional, Tuple


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                      # "dense" | "moe" | "mla" | "hybrid" | "ssm"
    modality: str = "text"           # "text" | "vlm" | "audio"
    num_layers: int = 0
    d_model: int = 0
    num_heads: int = 0
    num_kv_heads: int = 0
    head_dim: int = 0                # explicit; not always d_model // num_heads
    d_ff: int = 0                    # dense FFN width (0 for pure-SSM archs)
    vocab_size: int = 0

    # --- MoE ---
    num_experts: int = 0             # routed experts (0 => dense FFN)
    num_shared_experts: int = 0      # always-on experts (Qwen-MoE / Kimi style)
    top_k: int = 0
    moe_d_ff: int = 0                # per-expert FFN width
    first_k_dense: int = 0           # leading layers that use a dense FFN
    norm_topk_prob: bool = True      # renormalise the kept top-k gates
    # Routed experts this instance holds, (first, count) of a contiguous
    # range of the router's ``num_experts`` outputs; () holds them all.
    held_experts: Tuple[int, ...] = ()

    # --- latent attention (MLA, DeepSeek-V2): family "mla" ---
    # Every layer caches one row per token, [kv_a_norm(c_kv) | rope(k_pe)]
    # (kv_lora_rank + qk_rope_head_dim wide), shared by all heads.
    kv_lora_rank: int = 0
    q_lora_rank: int = 0             # 0 => a full-rank query projection
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # YaRN rope scaling; rope_factor 0 => plain rope on the rope part.
    rope_factor: float = 0.0
    rope_original_max_pos: int = 0
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 0.0

    # --- normalization / activation / positional ---
    norm_type: str = "rmsnorm"       # "rmsnorm" | "layernorm" | "nonparametric_ln"
    activation: str = "swiglu"       # "swiglu" | "geglu" | "gelu"
    qk_norm: bool = False
    positional: str = "rope"         # "rope" | "sinusoidal" | "none"
    rope_theta: float = 10_000.0
    tie_embeddings: bool = False
    norm_eps: float = 1e-5

    # --- hybrid (RecurrentGemma): repeating block pattern ---
    # e.g. ("rglru", "rglru", "attn"): one attention layer per two recurrent.
    block_pattern: Tuple[str, ...] = ()
    local_window: int = 0            # sliding-window size for local attention
    lru_width: int = 0               # RG-LRU recurrent width (0 => d_model)

    # --- ssm (xLSTM): which layer indices are sLSTM (rest mLSTM) ---
    slstm_every: int = 0             # i % slstm_every == slstm_every-1 => sLSTM
    mlstm_proj_factor: float = 2.0

    dtype: str = "bfloat16"

    # ------------------------------------------------------------------ #
    def __post_init__(self):
        if self.head_dim == 0 and self.num_heads:
            object.__setattr__(self, "head_dim", self.d_model // self.num_heads)

    @property
    def is_moe(self) -> bool:
        return self.num_experts > 0

    @property
    def is_latent(self) -> bool:
        """Latent (MLA) attention: one shared cache row per token."""
        return self.family == "mla"

    @property
    def latent_dim(self) -> int:
        """Width of one latent cache row (0 outside the latent family)."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def latent_pool_dim(self) -> int:
        """Width of a latent pool row: ``latent_dim`` rounded up to the
        TPU's 128 lanes (a 576-wide array is stored 640 wide on the chip
        anyway, and its DMAs must move whole lane tiles); zero-filled."""
        return -(-self.latent_dim // 128) * 128

    @property
    def expert_range(self) -> Tuple[int, int]:
        """(first, count) of the routed experts this instance holds."""
        return tuple(self.held_experts) or (0, self.num_experts)

    def kv_elems_per_token_layer(self) -> int:
        """Cached elements one token adds to one attention layer: K and V
        of every kv head, or the one latent row."""
        if self.is_latent:
            return self.latent_dim
        return 2 * self.num_kv_heads * self.head_dim

    def partial_elems(self) -> int:
        """Elements of one MicroAttention partial's ``o`` (all heads):
        per-head values, or per-head latent rows."""
        if self.is_latent:
            return self.num_heads * self.kv_lora_rank
        return self.num_heads * self.head_dim

    def query_elems(self) -> int:
        """Elements of the query a creditor receives (all heads): the
        rotated query, or its absorbed latent form."""
        if self.is_latent:
            return self.num_heads * self.latent_dim
        return self.num_heads * self.head_dim

    @property
    def has_attention(self) -> bool:
        return self.family != "ssm"

    @property
    def q_per_kv(self) -> int:
        return max(1, self.num_heads // max(1, self.num_kv_heads))

    def layer_kind(self, i: int) -> str:
        """Block type at layer index i: 'attn' | 'rglru' | 'mlstm' | 'slstm'."""
        if self.family == "ssm":
            if self.slstm_every and (i % self.slstm_every == self.slstm_every - 1):
                return "slstm"
            return "mlstm"
        if self.block_pattern:
            return self.block_pattern[i % len(self.block_pattern)]
        return "attn"

    # --- sizing helpers (used by the perf model and roofline) ----------- #
    def kv_bytes_per_token(self, bytes_per_el: int = 2) -> int:
        """KV-cache bytes per token across ALL layers (0 for O(1)-state archs)."""
        per_layer = self.kv_elems_per_token_layer() * bytes_per_el
        n_attn = sum(1 for i in range(self.num_layers) if self.layer_kind(i) == "attn")
        return per_layer * n_attn

    def param_count(self) -> int:
        """Total parameter count (approximate for ssm/hybrid internals)."""
        d, hd = self.d_model, self.head_dim
        n_emb = self.vocab_size * d * (1 if self.tie_embeddings else 2)
        total = n_emb
        glu = self.activation in ("swiglu", "geglu")
        for i in range(self.num_layers):
            kind = self.layer_kind(i)
            if kind == "attn" and self.is_latent:
                H, r = self.num_heads, self.kv_lora_rank
                qk = self.qk_nope_head_dim + self.qk_rope_head_dim
                total += d * H * qk + d * self.latent_dim       # q, kv_a
                total += r * H * (self.qk_nope_head_dim
                                  + self.v_head_dim)            # kv_b
                total += H * self.v_head_dim * d                # o
            elif kind == "attn":
                total += d * self.num_heads * hd * 2            # q, o
                total += d * self.num_kv_heads * hd * 2         # k, v
            elif kind == "rglru":
                w = self.lru_width or d
                total += 2 * d * w + w * d + 3 * w              # in x2, out, gates
            elif kind in ("mlstm", "slstm"):
                pf = self.mlstm_proj_factor if kind == "mlstm" else 4.0 / 3.0
                up = int(d * pf)
                total += 2 * d * up + up * d + 4 * up           # up/gate/down + gates
            # FFN
            if kind in ("attn", "rglru"):
                if self.is_moe and i >= self.first_k_dense:
                    n_e = self.expert_range[1] + self.num_shared_experts
                    per = (3 if glu else 2) * d * self.moe_d_ff
                    total += n_e * per + d * self.num_experts   # + router
                elif self.d_ff:
                    total += (3 if glu else 2) * d * self.d_ff
        return total

    def active_param_count(self) -> int:
        """Params touched per token (MoE: only top-k + shared experts)."""
        if not self.is_moe:
            return self.param_count()
        d = self.d_model
        glu = self.activation in ("swiglu", "geglu")
        per = (3 if glu else 2) * d * self.moe_d_ff
        n_moe_layers = self.num_layers - self.first_k_dense
        inactive = (self.expert_range[1] - self.top_k) * per * n_moe_layers
        return self.param_count() - inactive


@dataclass(frozen=True)
class ShapeConfig:
    name: str
    kind: str                        # "train" | "prefill" | "decode"
    seq_len: int
    global_batch: int


SHAPES = {
    "train_4k":    ShapeConfig("train_4k",    "train",   4_096,   256),
    "prefill_32k": ShapeConfig("prefill_32k", "prefill", 32_768,  32),
    "decode_32k":  ShapeConfig("decode_32k",  "decode",  32_768,  128),
    "long_500k":   ShapeConfig("long_500k",   "decode",  524_288, 1),
}


def reduce_config(cfg: ModelConfig, *, layers: Optional[int] = None) -> ModelConfig:
    """Shrink a config to CPU-smoke size while preserving family structure."""
    pat = len(cfg.block_pattern) or 1
    n_layers = layers if layers is not None else max(2, pat)
    if cfg.block_pattern:
        n_layers = max(n_layers, pat)          # at least one full pattern
    if cfg.slstm_every:
        n_layers = max(n_layers, cfg.slstm_every)
    heads = 4
    kv = max(1, heads * cfg.num_kv_heads // max(1, cfg.num_heads))
    return dataclasses.replace(
        cfg,
        name=cfg.name + "-smoke",
        num_layers=n_layers,
        d_model=64,
        num_heads=heads,
        num_kv_heads=kv,
        head_dim=16,
        d_ff=0 if cfg.d_ff == 0 else 128,
        vocab_size=256,
        num_experts=min(cfg.num_experts, 8),
        num_shared_experts=min(cfg.num_shared_experts, 1),
        top_k=min(cfg.top_k, 2),
        moe_d_ff=0 if cfg.moe_d_ff == 0 else 32,
        first_k_dense=min(cfg.first_k_dense, 1),
        local_window=0 if cfg.local_window == 0 else 32,
        lru_width=0 if cfg.lru_width == 0 else 64,
        slstm_every=cfg.slstm_every,
    )
