import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=8")
"""Subprocess helper: mesh-level serve_decode_step must reproduce the
single-device decode logits exactly, for BOTH pool layouts (tp_head and
seq_model) and an adversarial block placement. Exit 0 on success."""
import sys

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..",
                                "src"))

from repro.configs import get_smoke_config
from repro.models.model import decode_step, init_params
from repro.models.prefill import prefill
from repro.serving.sharded_step import ServeLayout, serve_decode_step
from repro.distributed.sharding import param_specs, validate_divisibility
from repro.launch.mesh import make_mesh


def check(arch: str, pool_axes, rng_seed=0, variant="baseline"):
    cfg = get_smoke_config(arch)
    key = jax.random.PRNGKey(rng_seed)
    params = init_params(key, cfg)
    mesh = make_mesh((2, 4), ("data", "model"))
    R, T = 4, 21                       # ragged: partial tail block
    bs = 8
    tokens_hist = jax.random.randint(key, (R, T), 0, cfg.vocab_size)
    new_tok = jax.random.randint(jax.random.fold_in(key, 1), (R,), 0,
                                 cfg.vocab_size)

    # Reference: single-device dense-cache decode.
    _, st = prefill(params, cfg, tokens_hist, max_len=T + 4)
    ref_logits, _ = decode_step(params, cfg, st, new_tok)

    # Build the paged pool with an adversarial placement: request r's
    # block j lives on shard (r + j) % NP.
    L, K, hd = cfg.num_layers, cfg.num_kv_heads, cfg.head_dim
    NP = int(np.prod([dict(zip(mesh.axis_names,
                               mesh.devices.shape))[a]
                      for a in pool_axes]))
    nblocks = -(-T // bs)
    per_shard = R * nblocks            # generous
    NB = per_shard
    pool_k = np.zeros((L, NP, NB, bs, K, hd), np.float32)
    pool_v = np.zeros_like(pool_k)
    MB = nblocks + 1
    tables = -np.ones((NP, R, MB), np.int32)
    tails = np.full((NP, R), bs, np.int32)
    next_free = np.zeros(NP, np.int32)
    kv_k = np.asarray(st.kv_k, np.float32)   # [L, R, maxlen, K, hd]
    kv_v = np.asarray(st.kv_v, np.float32)
    slot_of = {}
    for r in range(R):
        cnt = {}
        for j in range(nblocks):
            p = (r + j) % NP
            blk = int(next_free[p]); next_free[p] += 1
            c = cnt.get(p, 0); cnt[p] = c + 1
            tables[p, r, c] = blk
            lo, hi = j * bs, min((j + 1) * bs, T)
            pool_k[:, p, blk, :hi - lo] = kv_k[:, r, lo:hi]
            pool_v[:, p, blk, :hi - lo] = kv_v[:, r, lo:hi]
            slot_of[(r, j)] = (p, blk)
            if hi == T:
                tails[p, r] = hi - lo if hi - lo else bs
    # Tail-append target: last block has room (T % bs != 0).
    wblk = np.full((NP, R), NB, np.int32)    # dump by default
    woff = np.zeros((NP, R), np.int32)
    for r in range(R):
        p, blk = slot_of[(r, nblocks - 1)]
        wblk[p, r] = blk
        woff[p, r] = T % bs
        tails[p, r] += 1                     # include the new token
    nblk = (tables >= 0).sum(axis=2).astype(np.int32)

    layout = ServeLayout(batch_axes=("data",), pool_axes=pool_axes)
    pshapes = jax.eval_shape(lambda: params)
    pspecs = validate_divisibility(
        param_specs(cfg, pshapes, fsdp=False), pshapes, mesh)
    pool_spec = NamedSharding(mesh, P(None, pool_axes))
    itab = NamedSharding(mesh, P(pool_axes))
    bsh = NamedSharding(mesh, P("data"))

    jitted = jax.jit(
        lambda pr, pk, pv, tb, nb, tl, wb, wo, tk, ln: serve_decode_step(
            pr, cfg, layout, pk, pv, tb, nb, tl, wb, wo, tk, ln,
            capacity_factor=-1.0, return_logits=True),
        in_shardings=(
            jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs,
                         is_leaf=lambda x: isinstance(x, P)),
            pool_spec, pool_spec, itab, itab, itab, itab, itab, bsh, bsh),
    )
    dt = jnp.dtype(cfg.dtype)
    # Inputs are made before the mesh is set: under ``jax.set_mesh``
    # fresh arrays are committed replicated on it, which jit will not
    # silently reshard to the in_shardings above.
    args = (params, jnp.asarray(pool_k, dt), jnp.asarray(pool_v, dt),
            jnp.asarray(tables), jnp.asarray(nblk), jnp.asarray(tails),
            jnp.asarray(wblk), jnp.asarray(woff),
            new_tok, jnp.full((R,), T, jnp.int32))
    with jax.set_mesh(mesh):
        logits, pk_new, pv_new = jitted(*args)

    got = np.asarray(logits, np.float32)
    want = np.asarray(ref_logits, np.float32)
    np.testing.assert_allclose(got, want, atol=5e-2, rtol=5e-2)

    # The new token's KV must have landed in the right tail slots.
    pk_new = np.asarray(pk_new, np.float32)
    wrote = 0
    for r in range(R):
        p, blk = slot_of[(r, nblocks - 1)]
        assert np.abs(pk_new[:, p, blk, T % bs]).sum() > 0
        wrote += 1
    assert wrote == R
    print(f"OK {arch} pool_axes={pool_axes} NP={NP}")


if __name__ == "__main__":
    check("olmo-1b", ("data",))              # tp_head (kv % model == 0)
    check("qwen3-0.6b", ("data", "model"))   # seq_model (kv=2 < 4)
    check("qwen2-moe-a2.7b", ("data",))      # MoE + EP
    print("ALL OK")
