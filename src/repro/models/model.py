"""Model builder: init / full-sequence forward / single-token decode.

One entry point for all 10 assigned architectures. Layer stacks are
``jax.lax.scan`` over stacked parameter pytrees so the HLO stays compact at
512-way SPMD. Families:

  dense   — [ln1, attn, ln2, ffn] x L                  (scan)
  moe     — first_k_dense dense layers + [attn, moe] x L'  (scan)
  hybrid  — repeating block_pattern groups (+ leftover)    (scan of groups)
  ssm     — (slstm_every-1 mLSTM + 1 sLSTM) groups         (scan of groups)

The *global-view* forward here is what training and GSPMD lowering use;
the manual-collective serving step (Megatron TP + paged DistAttention)
lives in ``repro.serving.sharded_step`` and reuses the same blocks with a
TP-local config.
"""
from __future__ import annotations

from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.attention import sliding_window_mask_decode
from repro.core.attention import full_attention_decode
from repro.models.attention import (apply_attention_train, init_attention,
                                    make_causal_core, qkv_project)
from repro.models.common import (apply_ffn, apply_norm, embed_init,
                                 init_ffn, init_norm, dense_init,
                                 sinusoidal_embedding)
from repro.models.moe import apply_moe, init_moe, moe_aux_loss
from repro.models.rglru import (apply_rglru_block, init_rglru_block,
                                rglru_state_shape)
from repro.models.xlstm import (MLstmState, SLstmState, apply_mlstm_block,
                                apply_slstm_block, init_mlstm_block,
                                init_slstm_block, mlstm_state_init,
                                slstm_state_init)


# ===================================================================== #
# Init
# ===================================================================== #
def _init_attn_layer(key, cfg: ModelConfig, d_ff: Optional[int] = None,
                     moe: bool = False):
    ks = jax.random.split(key, 4)
    p = {"ln1": init_norm(cfg, cfg.d_model),
         "attn": init_attention(ks[0], cfg),
         "ln2": init_norm(cfg, cfg.d_model)}
    if moe:
        p["moe"] = init_moe(ks[1], cfg)
    else:
        p["ffn"] = init_ffn(ks[1], cfg, d_ff)
    return p


def _init_rglru_layer(key, cfg: ModelConfig):
    ks = jax.random.split(key, 2)
    return {"ln1": init_norm(cfg, cfg.d_model),
            "rglru": init_rglru_block(ks[0], cfg),
            "ln2": init_norm(cfg, cfg.d_model),
            "ffn": init_ffn(ks[1], cfg)}


def init_params(key, cfg: ModelConfig) -> Dict[str, Any]:
    ks = jax.random.split(key, 8)
    dtype = jnp.dtype(cfg.dtype)
    p: Dict[str, Any] = {
        "embed": embed_init(ks[0], cfg.vocab_size, cfg.d_model, dtype),
        "final_norm": init_norm(cfg, cfg.d_model),
    }
    if not cfg.tie_embeddings:
        p["unembed"] = dense_init(ks[1], cfg.d_model, cfg.vocab_size, dtype)

    if cfg.family == "dense":
        p["layers"] = jax.vmap(lambda k: _init_attn_layer(k, cfg))(
            jax.random.split(ks[2], cfg.num_layers))
    elif cfg.family == "moe":
        nd = cfg.first_k_dense
        if nd:
            p["dense_layers"] = jax.vmap(
                lambda k: _init_attn_layer(k, cfg, d_ff=cfg.d_ff))(
                jax.random.split(ks[3], nd))
        p["moe_layers"] = jax.vmap(
            lambda k: _init_attn_layer(k, cfg, moe=True))(
            jax.random.split(ks[2], cfg.num_layers - nd))
    elif cfg.family == "hybrid":
        pat = cfg.block_pattern
        n_groups = cfg.num_layers // len(pat)
        leftover = cfg.num_layers - n_groups * len(pat)

        def init_group(k):
            kk = jax.random.split(k, len(pat))
            g = {}
            for j, kind in enumerate(pat):
                g[f"{j}_{kind}"] = (_init_rglru_layer(kk[j], cfg)
                                    if kind == "rglru"
                                    else _init_attn_layer(kk[j], cfg))
            return g

        p["groups"] = jax.vmap(init_group)(jax.random.split(ks[2], n_groups))
        if leftover:
            def init_left(k, kinds=tuple(pat[:leftover])):
                kk = jax.random.split(k, len(kinds))
                return {f"{j}_{kind}": (_init_rglru_layer(kk[j], cfg)
                                        if kind == "rglru"
                                        else _init_attn_layer(kk[j], cfg))
                        for j, kind in enumerate(kinds)}
            p["leftover"] = init_left(ks[4])
    elif cfg.family == "ssm":
        se = cfg.slstm_every
        n_groups = cfg.num_layers // se

        def init_group(k):
            kk = jax.random.split(k, 2)
            return {
                "mlstm": jax.vmap(lambda kx: {
                    "ln": init_norm(cfg, cfg.d_model),
                    "blk": init_mlstm_block(kx, cfg)})(
                    jax.random.split(kk[0], se - 1)),
                "slstm": {"ln": init_norm(cfg, cfg.d_model),
                          "blk": init_slstm_block(kk[1], cfg)},
            }
        p["groups"] = jax.vmap(init_group)(jax.random.split(ks[2], n_groups))
    else:
        raise ValueError(cfg.family)
    return p


# ===================================================================== #
# Full-sequence forward (train / prefill lowering path)
# ===================================================================== #
def _attn_layer_fwd(lp, x, positions, cfg, core, *, moe=False,
                    capacity_factor=1.25, ep_groups=0):
    h = apply_norm(lp["ln1"], x, cfg)
    attn_out, kv = apply_attention_train(lp["attn"], h, positions, cfg, core)
    x = x + attn_out
    h = apply_norm(lp["ln2"], x, cfg)
    if moe:
        x = x + apply_moe(lp["moe"], h, cfg, capacity_factor,
                          ep_groups=ep_groups)
        aux = moe_aux_loss(lp["moe"], h, cfg)
    else:
        x = x + apply_ffn(lp["ffn"], h, cfg)
        aux = jnp.zeros((), jnp.float32)
    return x, kv, aux


def _rglru_layer_fwd(lp, x, cfg, state=None):
    h = apply_norm(lp["ln1"], x, cfg)
    mix, new_state = apply_rglru_block(lp["rglru"], h, cfg, state)
    x = x + mix
    h = apply_norm(lp["ln2"], x, cfg)
    return x + apply_ffn(lp["ffn"], h, cfg), new_state


def embed_tokens(params, cfg: ModelConfig, tokens=None, embeds=None,
                 positions=None):
    x = params["embed"][tokens] if embeds is None else embeds
    if cfg.positional == "sinusoidal":
        if positions is None:
            positions = jnp.arange(x.shape[1], dtype=jnp.int32)[None]
        x = x + sinusoidal_embedding(positions, cfg.d_model).astype(x.dtype)
    return x


def unembed(params, cfg: ModelConfig, x):
    x = apply_norm(params["final_norm"], x, cfg)
    w = params["embed"].T if cfg.tie_embeddings else params["unembed"]
    return x @ w


def forward(params, cfg: ModelConfig, tokens=None, embeds=None, *,
            backend: str = "xla", chunk: int = 512,
            capacity_factor: float = 1.25, interpret=None,
            remat: bool = False, ep_groups: int = 0,
            layer_constraints=None) -> Tuple[jax.Array, jax.Array]:
    """Full-sequence causal forward. Returns (logits [B,T,V], moe_aux).

    ``remat=True`` checkpoints each scanned layer (matmul outputs with no
    batch dims stay resident; everything else recomputes in backward) —
    the standard memory/compute trade for the train_4k cells.

    ``layer_constraints``: optional {stack_name: fn(lp)->lp} applied to
    each per-layer parameter slice INSIDE the scan body. This re-pins the
    slice to its FSDP sharding so GSPMD gathers weights one layer at a
    time instead of hoisting a full-stack all-gather out of the loop
    (which would need TBs of HBM at kimi-k2 scale).
    """
    B, T = (tokens.shape if embeds is None else embeds.shape[:2])
    positions = jnp.arange(T, dtype=jnp.int32)[None].repeat(B, 0)
    x = embed_tokens(params, cfg, tokens, embeds, positions)
    core = make_causal_core(cfg, backend=backend, chunk=chunk,
                            interpret=interpret)
    aux = jnp.zeros((), jnp.float32)
    lc = layer_constraints or {}
    def pin(name, lp):
        return lc[name](lp) if name in lc else lp

    def ckpt(fn):
        if not remat:
            return fn
        policy = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        return jax.checkpoint(fn, policy=policy)

    if cfg.family == "dense":
        @ckpt
        def body(x, lp):
            lp = pin("layers", lp)
            x, _, _ = _attn_layer_fwd(lp, x, positions, cfg, core)
            return x, None
        x, _ = jax.lax.scan(body, x, params["layers"])

    elif cfg.family == "moe":
        if cfg.first_k_dense:
            @ckpt
            def dbody(x, lp):
                lp = pin("dense_layers", lp)
                x, _, _ = _attn_layer_fwd(lp, x, positions, cfg, core)
                return x, None
            x, _ = jax.lax.scan(dbody, x, params["dense_layers"])

        @ckpt
        def mbody(carry, lp):
            lp = pin("moe_layers", lp)
            x, aux = carry
            x, _, a = _attn_layer_fwd(lp, x, positions, cfg, core, moe=True,
                                      capacity_factor=capacity_factor,
                                      ep_groups=ep_groups)
            return (x, aux + a), None
        (x, aux), _ = jax.lax.scan(mbody, (x, aux), params["moe_layers"])

    elif cfg.family == "hybrid":
        wcore = make_causal_core(cfg, backend=backend, chunk=chunk,
                                 window=cfg.local_window, interpret=interpret)
        pat = cfg.block_pattern

        @ckpt
        def gbody(x, gp):
            gp = pin("groups", gp)
            for j, kind in enumerate(pat):
                lp = gp[f"{j}_{kind}"]
                if kind == "rglru":
                    x, _ = _rglru_layer_fwd(lp, x, cfg)
                else:
                    x, _, _ = _attn_layer_fwd(lp, x, positions, cfg, wcore)
            return x, None
        x, _ = jax.lax.scan(gbody, x, params["groups"])
        if "leftover" in params:
            n_left = cfg.num_layers - (cfg.num_layers // len(pat)) * len(pat)
            for j, kind in enumerate(pat[:n_left]):
                lp = params["leftover"][f"{j}_{kind}"]
                if kind == "rglru":
                    x, _ = _rglru_layer_fwd(lp, x, cfg)
                else:
                    x, _, _ = _attn_layer_fwd(lp, x, positions, cfg, wcore)

    elif cfg.family == "ssm":
        @ckpt
        def gbody(x, gp):
            gp = pin("groups", gp)
            def mbody(x, mlp):
                h = apply_norm(mlp["ln"], x, cfg)
                y, _ = apply_mlstm_block(mlp["blk"], h, cfg)
                return x + y, None
            x, _ = jax.lax.scan(mbody, x, gp["mlstm"])
            h = apply_norm(gp["slstm"]["ln"], x, cfg)
            y, _ = apply_slstm_block(gp["slstm"]["blk"], h, cfg)
            return x + y, None
        x, _ = jax.lax.scan(gbody, x, params["groups"])
    else:
        raise ValueError(cfg.family)

    return unembed(params, cfg, x), aux


# ===================================================================== #
# Single-device decode (dense in-memory cache; tests + Python engine)
# ===================================================================== #
class DecodeState(NamedTuple):
    """Simple (non-paged) cache: full KV tensors + recurrent states."""
    kv_k: Any          # dict name -> [L, B, maxlen, K, hd] or None
    kv_v: Any
    lens: jax.Array    # [B] current sequence length
    rec: Any           # family-specific recurrent states (pytree) or None


def init_decode_state(cfg: ModelConfig, batch: int, max_len: int,
                      prefix_lens=None) -> DecodeState:
    dtype = jnp.dtype(cfg.dtype)
    K, hd = cfg.num_kv_heads, cfg.head_dim
    lens = (jnp.zeros((batch,), jnp.int32) if prefix_lens is None
            else prefix_lens)
    kv_k = kv_v = rec = None
    if cfg.family in ("dense", "moe"):
        L = cfg.num_layers
        kv_k = jnp.zeros((L, batch, max_len, K, hd), dtype)
        kv_v = jnp.zeros((L, batch, max_len, K, hd), dtype)
    elif cfg.family == "hybrid":
        n_attn = sum(1 for i in range(cfg.num_layers)
                     if cfg.layer_kind(i) == "attn")
        w = min(max_len, cfg.local_window)
        kv_k = jnp.zeros((n_attn, batch, w, K, hd), dtype)
        kv_v = jnp.zeros((n_attn, batch, w, K, hd), dtype)
        n_rg = cfg.num_layers - n_attn
        cshape, hshape = rglru_state_shape(cfg, batch)
        rec = (jnp.zeros((n_rg,) + cshape, dtype),
               jnp.zeros((n_rg,) + hshape, jnp.float32))
    elif cfg.family == "ssm":
        se = cfg.slstm_every
        ng = cfg.num_layers // se
        m0 = mlstm_state_init(cfg, batch)
        rec = {
            "mlstm": MLstmState(*[jnp.zeros((ng, se - 1) + a.shape, a.dtype)
                                  + a for a in m0]),
            "slstm": SLstmState(*[jnp.zeros((ng,) + a.shape, a.dtype) + a
                                  for a in slstm_state_init(cfg, batch)]),
        }
    return DecodeState(kv_k, kv_v, lens, rec)


def _cached_attn_decode(lp, x, state_k, state_v, lens, cfg, *, window=0):
    """x: [B, 1, d]; returns (out [B,1,d], k_new, v_new)."""
    B = x.shape[0]
    q, k, v = qkv_project(lp, x, lens[:, None], cfg)
    ql = q[:, 0]                                        # [B, H, hd]
    maxlen = state_k.shape[1]
    if window:
        pos = lens % maxlen                             # ring buffer
        k_cache = state_k.at[jnp.arange(B), pos].set(k[:, 0])
        v_cache = state_v.at[jnp.arange(B), pos].set(v[:, 0])
        kv_pos_rel = jnp.arange(maxlen, dtype=jnp.int32)[None].repeat(B, 0)
        # Absolute position of each ring slot given current write head.
        abs_pos = lens[:, None] - ((pos[:, None] - kv_pos_rel) % maxlen)
        mask = (abs_pos >= 0) & sliding_window_mask_decode(
            abs_pos, lens, window)
    else:
        k_cache = state_k.at[jnp.arange(B), lens].set(k[:, 0])
        v_cache = state_v.at[jnp.arange(B), lens].set(v[:, 0])
        mask = (jnp.arange(maxlen, dtype=jnp.int32)[None]
                <= lens[:, None])
    out = full_attention_decode(ql, k_cache, v_cache, mask)
    out = out.reshape(B, 1, -1).astype(x.dtype) @ lp["wo"]
    return out, k_cache, v_cache


def _attn_layer_decode(lp, x, ck, cv, lens, cfg, *, moe=False, window=0):
    h = apply_norm(lp["ln1"], x, cfg)
    out, ck, cv = _cached_attn_decode(lp["attn"], h, ck, cv, lens, cfg,
                                      window=window)
    x = x + out
    h = apply_norm(lp["ln2"], x, cfg)
    if moe:
        x = x + apply_moe(lp["moe"], h, cfg, capacity_factor=-1.0)
    else:
        x = x + apply_ffn(lp["ffn"], h, cfg)
    return x, ck, cv


def decode_step(params, cfg: ModelConfig, state: DecodeState,
                tokens: jax.Array) -> Tuple[jax.Array, DecodeState]:
    """One decode step for a batch. tokens: [B] -> (logits [B,V], state)."""
    x = embed_tokens(params, cfg, tokens[:, None], None,
                     positions=state.lens[:, None])
    lens = state.lens

    if cfg.family in ("dense", "moe"):
        ck_all, cv_all = state.kv_k, state.kv_v
        if cfg.family == "dense":
            def body(x, xs):
                lp, ck, cv = xs
                x, ck, cv = _attn_layer_decode(lp, x, ck, cv, lens, cfg)
                return x, (ck, cv)
            x, (ck_all, cv_all) = jax.lax.scan(
                body, x, (params["layers"], ck_all, cv_all))
        else:
            nd = cfg.first_k_dense
            if nd:
                def dbody(x, xs):
                    lp, ck, cv = xs
                    x, ck, cv = _attn_layer_decode(lp, x, ck, cv, lens, cfg)
                    return x, (ck, cv)
                x, (ck_d, cv_d) = jax.lax.scan(
                    dbody, x, (params["dense_layers"],
                               ck_all[:nd], cv_all[:nd]))

            def mbody(x, xs):
                lp, ck, cv = xs
                x, ck, cv = _attn_layer_decode(lp, x, ck, cv, lens, cfg,
                                               moe=True)
                return x, (ck, cv)
            x, (ck_m, cv_m) = jax.lax.scan(
                mbody, x, (params["moe_layers"], ck_all[nd:], cv_all[nd:]))
            ck_all = jnp.concatenate([ck_d, ck_m], 0) if nd else ck_m
            cv_all = jnp.concatenate([cv_d, cv_m], 0) if nd else cv_m
        new_state = DecodeState(ck_all, cv_all, lens + 1, None)

    elif cfg.family == "hybrid":
        conv_c, lru_h = state.rec
        ck_all, cv_all = state.kv_k, state.kv_v
        ai = ri = 0
        new_ck, new_cv, new_cc, new_h = [], [], [], []
        for i in range(cfg.num_layers):
            kind = cfg.layer_kind(i)
            lp = _layer_params(params, cfg, i)
            if kind == "attn":
                x, ck, cv = _attn_layer_decode(
                    lp, x, ck_all[ai], cv_all[ai], lens, cfg,
                    window=cfg.local_window)
                new_ck.append(ck); new_cv.append(cv)
                ai += 1
            else:
                h = apply_norm(lp["ln1"], x, cfg)
                mix, (cc, hh) = apply_rglru_block(
                    lp["rglru"], h, cfg, (conv_c[ri], lru_h[ri]),
                    decode=True)
                x = x + mix
                h2 = apply_norm(lp["ln2"], x, cfg)
                x = x + apply_ffn(lp["ffn"], h2, cfg)
                new_cc.append(cc); new_h.append(hh)
                ri += 1
        new_state = DecodeState(jnp.stack(new_ck), jnp.stack(new_cv),
                                lens + 1,
                                (jnp.stack(new_cc), jnp.stack(new_h)))

    elif cfg.family == "ssm":
        rec = state.rec

        def gbody(x, xs):
            gp, mst, sst = xs

            def mbody(x, ms):
                mlp, st = ms
                h = apply_norm(mlp["ln"], x, cfg)
                y, st = apply_mlstm_block(mlp["blk"], h, cfg,
                                          MLstmState(*st), decode=True)
                return x + y, tuple(st)
            x, mst = jax.lax.scan(mbody, x, (gp["mlstm"], tuple(mst)))
            h = apply_norm(gp["slstm"]["ln"], x, cfg)
            y, sst = apply_slstm_block(gp["slstm"]["blk"], h, cfg,
                                       SLstmState(*sst), decode=True)
            return x + y, (mst, tuple(sst))

        x, (mst, sst) = jax.lax.scan(
            gbody, x, (params["groups"], tuple(rec["mlstm"]),
                       tuple(rec["slstm"])))
        new_state = DecodeState(None, None, lens + 1,
                                {"mlstm": MLstmState(*mst),
                                 "slstm": SLstmState(*sst)})
    else:
        raise ValueError(cfg.family)

    logits = unembed(params, cfg, x[:, 0])
    return logits, new_state


def _layer_params(params, cfg: ModelConfig, i: int):
    """Extract layer-i params from the stacked pytrees (hybrid family)."""
    pat = cfg.block_pattern
    ng = cfg.num_layers // len(pat)
    g, j = divmod(i, len(pat))
    kind = pat[j]
    if g < ng:
        return jax.tree.map(lambda a: a[g], params["groups"][f"{j}_{kind}"])
    return params["leftover"][f"{j}_{kind}"]
