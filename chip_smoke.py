"""Smoke run of the DistAttention server on TPU chips, in one process.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # four chips: the global KV pool on a mesh

One chip, at the full width of olmo-1b (random weights from ``--seed``):
  1. the paged decode and prefill Pallas kernels run natively and are
     compared with their jnp references;
  2. the server's real ``decode_step_paged`` is compiled and must carry
     the kernel (``tpu_custom_call``);
  3. ``LLMServer`` answers three short prompts and one that exceeds an
     instance's local quota, so its KV spans onto the other instance.
With ``--chips 4`` only the four-chip path runs: the global KV pool
sharded over a (4, 1) mesh at scale in bf16, then the float32 check that
the mesh and one device give identical greedy token streams.

The script fails, printing no result, when JAX finds no TPU; it never
falls back to the CPU. Times it prints are of one run, compilation
included; they are not metrics. The last line of stdout is one JSON
object: {"ok": true, "device": {"platform", "kind", "count"}}.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
ARCH = "olmo-1b"
N_NEW = 32

# Native kernel vs jnp reference (reference at "highest" matmul
# precision), on bf16 pools: max abs error of the normalized output and
# of the running max m, and max relative error of the denominator l.
KERNEL_TOL = 2e-2

# One chip: two instances, each with a 2 GiB pool (K and V).
ONE_CHIP = dict(n_instances=2, max_batch=16, pool_blocks=1024, block_size=16,
                max_local_len=8192, prefill_chunk=256)
SHORT_PROMPTS = (128, 512, 1024)
LONG_PROMPT = 12_288                 # > max_local_len: spans a creditor

# Four chips: one instance per chip, one global pool, 4 GiB per chip.
# The paged prefill kernel's compile time grows steeply with the chunk
# (about 7 s at 128 tokens, 26 s at 256, 2 min at 512 for one variant),
# and every table bucket is a variant: 128 keeps the call short.
FOUR_CHIPS = dict(n_instances=4, global_pool=True, max_batch=16,
                  pool_blocks=2048, block_size=16, max_local_len=16_384,
                  prefill_chunk=128)
FOUR_LONG_PROMPT = 40_000            # > max_local_len: spans >= 2 ranks
# The float32 comparison: 2,048 tokens per rank, 1,024 local.
COMPARE = dict(FOUR_CHIPS, pool_blocks=128, max_local_len=1024)
COMPARE_LONG_PROMPT = 3_000


class SmokeFailure(Exception):
    """A phase's check did not hold."""


def check(cond, msg):
    """Raise ``SmokeFailure(msg)`` unless ``cond``."""
    if not cond:
        raise SmokeFailure(msg)


def _partial_errors(got, want):
    """(normalized-output abs, m abs, l relative) errors of (o, m, l)."""
    import numpy as np
    go, gm, gl = (np.asarray(a, np.float32) for a in got)
    wo, wm, wl = (np.asarray(a, np.float32) for a in want)
    out = np.abs(go / gl[..., None] - wo / wl[..., None]).max()
    return (float(out), float(np.abs(gm - wm).max()),
            float(np.abs(gl / wl - 1.0).max()))


def check_kernels(cfg, *, seed, R=16, NB=1024, bs=16, MB=64, C=256):
    """Paged decode and prefill kernels vs their jnp references."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops

    H, K, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    kq, kc, kk, kv = jax.random.split(jax.random.PRNGKey(seed), 4)

    def normal(key, shape):
        return jax.random.normal(key, shape, jnp.float32).astype(jnp.bfloat16)

    pool_k, pool_v = normal(kk, (NB, bs, K, hd)), normal(kv, (NB, bs, K, hd))
    rng = np.random.default_rng(seed)
    # Every request holds 1..MB blocks of a shuffled pool; the last one
    # is partly written.
    n_blk = rng.integers(1, MB + 1, size=R)
    perm = rng.permutation(NB)
    check(n_blk.sum() <= NB, "kernel check: pool too small for the tables")
    table = -np.ones((R, MB), np.int32)
    start = 0
    for r, n in enumerate(n_blk):
        table[r, :n] = perm[start:start + n]
        start += n
    tails = rng.integers(1, bs + 1, size=R).astype(np.int32)
    table, tails = jnp.asarray(table), jnp.asarray(tails)

    q = normal(kq, (R, H, hd))
    got = ops.paged_micro_attention(q, pool_k, pool_v, table, tails,
                                    backend="pallas", interpret=False)
    with jax.default_matmul_precision("highest"):
        want = ops.paged_micro_attention_jnp(q, pool_k, pool_v, table, tails)
    errs = _partial_errors(got, want)
    print(f"kernel paged_micro_attention (R {R}, H {H}, K {K}, hd {hd}, "
          f"bs {bs}, MB {MB}): max err out {errs[0]:.3e} m {errs[1]:.3e} "
          f"l(rel) {errs[2]:.3e} (tolerance {KERNEL_TOL})", flush=True)
    check(max(errs) <= KERNEL_TOL, "decode kernel disagrees with reference")

    qc = normal(kc, (C, H, hd))
    got = ops.paged_prefill_attention(qc, pool_k, pool_v, table[0],
                                      tails[0], backend="pallas",
                                      interpret=False)
    with jax.default_matmul_precision("highest"):
        want = ops.paged_prefill_attention_jnp(qc, pool_k, pool_v, table[0],
                                               tails[0])
    errs = _partial_errors(got, want)
    print(f"kernel paged_prefill_attention (C {C}, {int(n_blk[0])} blocks): "
          f"max err out {errs[0]:.3e} m {errs[1]:.3e} l(rel) {errs[2]:.3e} "
          f"(tolerance {KERNEL_TOL})", flush=True)
    check(max(errs) <= KERNEL_TOL, "prefill kernel disagrees with reference")


def check_decode_step_has_kernel(params, cfg, sc):
    """Compile the server's decode step (owner + one creditor pool) and
    require the Pallas kernel in it."""
    import jax
    import jax.numpy as jnp

    from repro.models.prefill import decode_step_paged
    from repro.serving.kvpool import table_bucket

    B, P = sc.max_batch, 2
    MB = table_bucket(sc.max_local_len // sc.block_size)
    pool = jax.ShapeDtypeStruct(
        (cfg.num_layers, sc.pool_blocks, sc.block_size, cfg.num_kv_heads,
         cfg.head_dim), jnp.dtype(cfg.dtype))

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32)

    def step(params, tokens, lens, pk, pv, rk, rv, tables, tails, wb, wo):
        return decode_step_paged(params, cfg, tokens, lens, pk, pv, tables,
                                 tails, wb, wo, remote_pools=((rk, rv),))

    text = jax.jit(step).lower(
        params, i32(B), i32(B), pool, pool, pool, pool, i32(P, B, MB),
        i32(P, B), i32(B), i32(B)).compile().as_text()
    found = "tpu_custom_call" in text
    print(f"decode_step_paged (B {B}, {P} pools, MB {MB}) compiled: "
          f"tpu_custom_call {'present' if found else 'MISSING'}", flush=True)
    check(found, "the compiled decode step carries no Pallas kernel")


def _prompts(cfg, lengths, seed):
    import numpy as np
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, size=n).tolist()
            for n in lengths]


def _check_allocators_clean(cluster):
    for i, eng in cluster.engines.items():
        a = eng.rmanager.pool.alloc
        check(a.free_count == a.num_blocks and a.reserved == 0,
              f"instance {i}: allocator not clean (used {a.used_count}, "
              f"reserved {a.reserved}, num_blocks {a.num_blocks})")
    print(f"allocators clean on all {len(cluster.engines)} instances "
          f"(free == num_blocks, reserved == 0)", flush=True)


def serve(params, cfg, sc, lengths, *, seed, mesh=None, layout=None):
    """Answer greedy requests through ``LLMServer``; require every one to
    finish with N_NEW tokens, a creditor span, clean allocators."""
    from repro.launch.identity import ranks_spanned
    from repro.models.prefill import (paged_trace_count,
                                      prefill_chunk_trace_count)
    from repro.serving import LLMServer, RequestState, SamplingParams
    from repro.serving.sharded_step import global_trace_count

    server = LLMServer(params, cfg, sc, mesh=mesh, layout=layout)
    prompts = _prompts(cfg, lengths, seed)
    handles = [server.submit(p, SamplingParams(max_new_tokens=N_NEW))
               for p in prompts]
    spanned, steps = 1, 0
    while not all(h.done for h in handles):
        check(steps < 10_000, "server made no progress in 10,000 steps")
        server.step()
        spanned = max(spanned, ranks_spanned(server.cluster))
        steps += 1
    for n, h in zip(lengths, handles):
        m = h.metrics
        print(f"request prompt {n} tokens: {h.status.name}, "
              f"{len(h.result())} tokens; ttft {m['ttft']:.3f} s, wall "
              f"{m['e2e']:.3f} s (one-off smoke run, compilation included; "
              f"not a metric)", flush=True)
        check(h.status == RequestState.FINISHED and
              len(h.result()) == N_NEW,
              f"prompt of {n} tokens did not finish with {N_NEW} tokens")
    print(f"{steps} server steps; most ranks one request spanned: "
          f"{spanned}; step traces so far: decode {paged_trace_count()}, "
          f"prefill {prefill_chunk_trace_count()}, global "
          f"{global_trace_count()}", flush=True)
    check(spanned >= 2, "no request's KV spanned a creditor")
    _check_allocators_clean(server.cluster)
    return server


def one_chip(seed):
    """Kernels, the compiled decode step, and the server on one chip."""
    import jax

    from repro.configs import get_config
    from repro.models.model import init_params
    from repro.serving import ServingConfig

    cfg = get_config(ARCH)
    check_kernels(cfg, seed=seed)
    params = init_params(jax.random.PRNGKey(seed), cfg)
    sc = ServingConfig.v5e(**ONE_CHIP)
    check_decode_step_has_kernel(params, cfg, sc)
    serve(params, cfg, sc, SHORT_PROMPTS + (LONG_PROMPT,), seed=seed)
    stats = jax.devices()[0].memory_stats() or {}
    print(f"peak_bytes_in_use {stats.get('peak_bytes_in_use')}", flush=True)


def four_chips(seed):
    """The global KV pool sharded over four chips, then the float32
    mesh-vs-one-device token-stream identity."""
    import jax

    from repro.configs import get_config
    from repro.launch.identity import mesh_matches_one_device
    from repro.launch.mesh import make_mesh
    from repro.models.model import init_params
    from repro.serving import ServingConfig
    from repro.serving.sharded_step import ServeLayout

    devices = jax.devices()[:4]
    check(len(devices) == 4, f"--chips 4 needs 4 devices, found "
          f"{len(jax.devices())}")
    mesh = make_mesh((4, 1), ("data", "model"), devices=devices)
    layout = ServeLayout(batch_axes=("data",), pool_axes=("data",))

    cfg = get_config(ARCH)
    params = init_params(jax.random.PRNGKey(seed), cfg)
    sc = ServingConfig.v5e(**FOUR_CHIPS)
    server = serve(params, cfg, sc, SHORT_PROMPTS + (FOUR_LONG_PROMPT,),
                   seed=seed, mesh=mesh, layout=layout)
    shards = server.cluster.gpool.k.addressable_shards
    ranks = sorted(s.index[0].indices(sc.n_instances)[:2] for s in shards)
    check(len({s.device for s in shards}) == 4 and
          ranks == [(r, r + 1) for r in range(4)],
          f"gpool.k is not one rank per device: {ranks}")
    print("gpool.k: each of the 4 devices holds exactly one rank's shard "
          f"{tuple(shards[0].data.shape)}", flush=True)
    for d in devices:
        print(f"device {d.id} bytes_in_use "
              f"{(d.memory_stats() or {}).get('bytes_in_use')}", flush=True)
    del server, shards, params
    gc.collect()

    cfg32 = dataclasses.replace(cfg, dtype="float32")
    with jax.default_matmul_precision("highest"):
        params32 = init_params(jax.random.PRNGKey(seed), cfg32)
        prompts = _prompts(cfg, SHORT_PROMPTS + (COMPARE_LONG_PROMPT,), seed)
        on_mesh, one_dev = mesh_matches_one_device(
            params32, cfg32, ServingConfig.v5e(**COMPARE), prompts, N_NEW,
            mesh, layout)
    print(f"float32 greedy streams identical, mesh vs one device: "
          f"{len(prompts)} requests x {N_NEW} tokens; ranks spanned "
          f"{on_mesh.max_ranks_spanned} (mesh), "
          f"{one_dev.max_ranks_spanned} (one device)", flush=True)
    check(min(on_mesh.max_ranks_spanned, one_dev.max_ranks_spanned) >= 2,
          "the long prompt did not span two ranks")


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"chip_smoke: no src/repro beside {__file__}; run it from a "
              f"checkout of the repository", file=sys.stderr)
        return 1
    sys.path.insert(0, src)

    import jax

    from repro.launch.compile_cache import enable_compile_cache

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found platform {dev.platform!r} "
              f"({dev.device_kind}), not a TPU; nothing was run",
              file=sys.stderr)
        return 1
    count = len(jax.devices())
    print(f"device {dev.device_kind} ({dev.platform}), count {count}; "
          f"running the {args.chips}-chip path", flush=True)

    cache_dir = enable_compile_cache()
    cache = {"hits": 0, "misses": 0}

    def on_event(event, **_):
        for k in cache:
            if event == f"/jax/compilation_cache/cache_{k}":
                cache[k] += 1

    jax.monitoring.register_event_listener(on_event)
    t0 = time.monotonic()
    try:
        if args.chips == 4:
            four_chips(args.seed)
        else:
            one_chip(args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(f"compile cache {cache_dir}: {cache['hits']} hits, "
          f"{cache['misses']} misses; whole run {time.monotonic() - t0:.1f} s "
          f"(one-off, not a metric)", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
