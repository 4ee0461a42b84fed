"""Mixture-of-Experts FFN: shared + routed experts, top-k, EP-shardable.

Dispatch is sort-based with per-expert capacity (the TPU-friendly
formulation): (token, k) assignments are sorted by expert id, each expert
receives a fixed-capacity [E, C, d] buffer (scatter-add), expert FFNs run
as one grouped einsum over the expert axis (shardable over the ``model``
mesh axis = expert parallelism), and outputs gather back with the gate
weights. Overflow beyond capacity drops tokens (standard); tests use a
no-drop capacity. FLOPs stay O(N * top_k * d * d_ff) — active experts
only — unlike a dense all-experts dispatch.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.common import dense_init


def init_moe(key, cfg: ModelConfig):
    d, dff = cfg.d_model, cfg.moe_d_ff
    E, SE = cfg.num_experts, cfg.num_shared_experts
    dtype = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 3)
    glu = cfg.activation in ("swiglu", "geglu")

    def bank(key, n):
        kk = jax.random.split(key, 3)
        std_i, std_o = d ** -0.5, dff ** -0.5
        p = {"wi": (jax.random.normal(kk[0], (n, d, dff), jnp.float32)
                    * std_i).astype(dtype),
             "wo": (jax.random.normal(kk[2], (n, dff, d), jnp.float32)
                    * std_o).astype(dtype)}
        if glu:
            p["wg"] = (jax.random.normal(kk[1], (n, d, dff), jnp.float32)
                       * std_i).astype(dtype)
        return p

    p = {"router": dense_init(ks[0], d, E, dtype),
         "experts": bank(ks[1], cfg.expert_range[1])}
    if SE:
        p["shared"] = bank(ks[2], SE)
    return p


def _expert_ffn(bank, x_e, cfg: ModelConfig, ep_pin: bool = False):
    """x_e: [E, C, d] tokens grouped per expert -> [E, C, d].

    ``ep_pin``: explicitly gather each rank's OWN experts over the fsdp
    (data) axis before the einsum. Without it, the einsum's lhs-C(data) /
    rhs-d(data) conflict makes GSPMD gather ALL experts to every device
    (measured 33.8 GB/layer vs 2.1 GB for the rank's 24 — §Perf-3).
    """
    wi, wo = bank["wi"], bank["wo"]
    wg = bank.get("wg")
    if ep_pin:
        from jax.sharding import PartitionSpec as P
        def pin(w):
            return jax.lax.with_sharding_constraint(
                w, P("model", None, None))
        wi, wo = pin(wi), pin(wo)
        wg = pin(wg) if wg is not None else None
    h = jnp.einsum("ecd,edf->ecf", x_e, wi)
    if cfg.activation == "swiglu":
        h = jax.nn.silu(h) * jnp.einsum("ecd,edf->ecf", x_e, wg)
    elif cfg.activation == "geglu":
        h = jax.nn.gelu(h) * jnp.einsum("ecd,edf->ecf", x_e, wg)
    else:
        h = jax.nn.gelu(h)
    return jnp.einsum("ecf,efd->ecd", h, wo)


def moe_capacity(n_tokens: int, cfg: ModelConfig,
                 capacity_factor: float = 1.25) -> int:
    """Per-expert buffer size C."""
    if capacity_factor <= 0:                       # no-drop mode (tests)
        return n_tokens
    c = math.ceil(n_tokens * cfg.top_k / cfg.num_experts * capacity_factor)
    return max(cfg.top_k, min(n_tokens, c))


def route(params, xt, cfg: ModelConfig):
    """Router of ONE token group, xt [N, d]: the softmax over all
    ``num_experts`` outputs, its top-k (gates [N, k] float32, expert ids
    [N, k]), the kept gates renormalised unless ``norm_topk_prob`` is
    off."""
    logits = (xt @ params["router"]).astype(jnp.float32)      # [N, E]
    gates, idx = jax.lax.top_k(jax.nn.softmax(logits, -1), cfg.top_k)
    if cfg.norm_topk_prob:
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    return gates, idx


def held_assignments(idx, cfg: ModelConfig) -> jax.Array:
    """Per row, how many of its top-k expert ids [N, k] fall in the
    held range (int32 [N])."""
    lo, n = cfg.expert_range
    return jnp.sum((idx >= lo) & (idx < lo + n), axis=-1, dtype=jnp.int32)


def _dispatch_combine(xt, gates, idx, cfg: ModelConfig, C: int,
                      expert_fn) -> jax.Array:
    """Sort-based dispatch for ONE token group to the held experts:
    every assignment outside ``cfg.expert_range`` is dropped (weight 0,
    no capacity), the rest run on the held bank. xt [N, d], gates and
    expert ids idx [N, k] from ``route`` -> [N, d]."""
    lo, n = cfg.expert_range
    N, d = xt.shape
    topk = cfg.top_k
    local = idx.reshape(-1) - lo                              # [N*k]
    flat_e = jnp.where((local >= 0) & (local < n), local, n)  # n = dropped
    flat_t = jnp.repeat(jnp.arange(N, dtype=jnp.int32), topk)
    flat_g = gates.reshape(-1)
    order = jnp.argsort(flat_e)                               # stable
    se, st, sg = flat_e[order], flat_t[order], flat_g[order]

    counts = jnp.zeros((n + 1,), jnp.int32).at[flat_e].add(1)
    starts = jnp.cumsum(counts) - counts                      # exclusive
    pos = jnp.arange(N * topk, dtype=jnp.int32) - starts[se]
    keep = (pos < C) & (se < n)
    safe_p = jnp.where(keep, pos, 0)
    safe_e = jnp.where(keep, se, 0)

    tok = xt[st] * keep[:, None].astype(xt.dtype)             # [N*k, d]
    buf = jnp.zeros((n, C, d), xt.dtype).at[safe_e, safe_p].add(tok)
    y_buf = expert_fn(buf)                                    # [n, C, d]
    w = (sg * keep).astype(xt.dtype)
    return jnp.zeros((N, d), xt.dtype).at[st].add(
        y_buf[safe_e, safe_p] * w[:, None])


def apply_moe(params, x: jax.Array, cfg: ModelConfig,
              capacity_factor: float = 1.25,
              ep_groups: int = 0, with_held: bool = False):
    """x: [B, T, d] -> [B, T, d]. Top-k routed + always-on shared experts.

    An instance that holds a share of the experts (``cfg.held_experts``)
    routes over all of them and computes only its own: the rest of each
    row's top-k is dropped. ``with_held`` also returns, per row, how
    many of its top-k assignments landed on held experts (int32 [B, T]).

    ``ep_groups > 1`` enables the 2D expert-parallel formulation for mesh
    execution (production axes "data" x "model"): the token stream splits
    into ``ep_groups`` DATA-LOCAL groups, each sorting/scattering its own
    tokens (a single global argsort/scatter otherwise makes GSPMD
    materialize terabyte-scale gathered intermediates — EXPERIMENTS.md
    §Perf-3), and the grouped buffers are pinned to
    [E->model, group->data] for the expert einsum, so tokens never leave
    their data rank and expert weights move only as per-layer FSDP
    gathers.
    """
    from jax.sharding import PartitionSpec as P
    wsc = jax.lax.with_sharding_constraint
    B, T, d = x.shape
    E = cfg.num_experts
    xt = x.reshape(-1, d)                                     # [N, d]
    N = xt.shape[0]

    held = None
    if ep_groups and ep_groups > 1 and N % ep_groups == 0:
        assert cfg.expert_range == (0, E), \
            "an expert share runs without the expert-parallel groups"
        G = ep_groups
        Ng = N // G
        Cg = moe_capacity(Ng, cfg, capacity_factor)
        xg = wsc(xt.reshape(G, Ng, d), P("data", None, None))

        def expert_fn(buf_g):          # [G, E, Cg, d] -> same
            b = jnp.moveaxis(buf_g, 1, 0)                     # [E, G, Cg, d]
            b = wsc(b, P("model", "data", None, None))
            h = _expert_ffn(params["experts"],
                            b.reshape(E, G * Cg, d), cfg, ep_pin=True)
            h = h.reshape(E, G, Cg, d)
            h = wsc(h, P("model", "data", None, None))
            return jnp.moveaxis(h, 0, 1)                      # [G, E, Cg, d]

        # Two-phase: per-group dispatch -> joint expert compute (E over
        # "model") -> per-group combine.
        E_, topk = cfg.num_experts, cfg.top_k

        def phase1(xt_i):
            gates, idx = route(params, xt_i, cfg)
            flat_e = idx.reshape(-1)
            flat_t = jnp.repeat(jnp.arange(Ng, dtype=jnp.int32), topk)
            flat_g = gates.reshape(-1)
            order = jnp.argsort(flat_e)
            se, st, sg = flat_e[order], flat_t[order], flat_g[order]
            counts = jnp.zeros((E_,), jnp.int32).at[flat_e].add(1)
            starts = jnp.cumsum(counts) - counts
            pos = jnp.arange(Ng * topk, dtype=jnp.int32) - starts[se]
            keep = pos < Cg
            safe_p = jnp.where(keep, pos, 0)
            tok = xt_i[st] * keep[:, None].astype(xt_i.dtype)
            buf = jnp.zeros((E_, Cg, d), xt_i.dtype).at[se, safe_p].add(tok)
            return buf, (se, st, sg, keep, safe_p)

        buf_g, meta = jax.vmap(phase1)(xg)
        buf_g = wsc(buf_g, P("data", None, None, None))
        y_buf_g = expert_fn(buf_g)
        y_buf_g = wsc(y_buf_g, P("data", None, None, None))

        def phase2(y_buf, xt_i, m):
            se, st, sg, keep, safe_p = m
            w = (sg * keep).astype(xt_i.dtype)
            return jnp.zeros((Ng, d), xt_i.dtype).at[st].add(
                y_buf[se, safe_p] * w[:, None])

        y = jax.vmap(phase2)(y_buf_g, xg, meta).reshape(N, d)
    else:
        C = moe_capacity(N, cfg, capacity_factor)
        gates, idx = route(params, xt, cfg)
        held = held_assignments(idx, cfg)
        y = _dispatch_combine(xt, gates, idx, cfg, C,
                              lambda buf: _expert_ffn(params["experts"],
                                                      buf, cfg))

    if cfg.num_shared_experts:
        xs = jnp.broadcast_to(xt, (cfg.num_shared_experts, N, d))
        y = y + _expert_ffn(params["shared"], xs, cfg).sum(0).astype(xt.dtype)
    y = y.reshape(B, T, d).astype(x.dtype)
    if not with_held:
        return y
    if held is None:
        held = jnp.full((N,), cfg.top_k, jnp.int32)
    return y, held.reshape(B, T)


def moe_aux_loss(params, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Load-balancing auxiliary loss (Switch-style) for training."""
    xt = x.reshape(-1, x.shape[-1])
    logits = (xt @ params["router"]).astype(jnp.float32)
    probs = jax.nn.softmax(logits, -1)
    _, idx = jax.lax.top_k(probs, cfg.top_k)
    hard = jax.nn.one_hot(idx, cfg.num_experts).sum(1)        # [N, E]
    return cfg.num_experts * jnp.sum(hard.mean(0) * probs.mean(0))
