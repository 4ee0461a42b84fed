import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=8")
"""Subprocess helper: the mesh-sharded GLOBAL KV pool must generate the
same greedy tokens as the per-instance cluster AND the dense-cache
oracle, dense + moe, with a mid-stream StripedMove relocating blocks
between rank slices of the one pool tensor. Exit 0 on success.

The mesh-vs-one-device half is ``repro.launch.identity``, the same check
``chip_smoke.py --chips 4`` makes on four TPU chips."""
import dataclasses
import sys

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..",
                                "src"))

from repro.configs import get_smoke_config
from repro.launch.identity import mesh_matches_one_device, serve_greedy
from repro.launch.mesh import make_mesh
from repro.models.model import decode_step, init_params
from repro.models.prefill import prefill
from repro.serving import ServingConfig
from repro.serving.sharded_step import ServeLayout


def greedy_ref(params, cfg, prompt, n_new):
    tokens = jnp.asarray([prompt], jnp.int32)
    logits, state = prefill(params, cfg, tokens,
                            max_len=len(prompt) + n_new + 2)
    out = [int(jnp.argmax(logits[0]))]
    for _ in range(n_new - 1):
        lg, state = decode_step(params, cfg, state,
                                jnp.asarray([out[-1]], jnp.int32))
        out.append(int(jnp.argmax(lg[0])))
    return out


def check(arch, n_inst, pool_axes, mesh_shape):
    # float32: the three implementations reassociate the LSE merge
    # differently, and greedy argmax must not flip on rounding noise.
    cfg = dataclasses.replace(get_smoke_config(arch), dtype="float32")
    params = init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(5)
    # 40 tokens > max_local_len=32 forces creditor striping at admission
    # AND reactive StripedMoves mid-decode (= intra-tensor slice copies
    # between rank shards in global mode).
    prompts = [list(rng.integers(0, cfg.vocab_size, size=40)),
               list(rng.integers(0, cfg.vocab_size, size=9))]
    n_new = 12
    refs = [greedy_ref(params, cfg, p, n_new) for p in prompts]

    config = ServingConfig.smoke(n_instances=n_inst, max_batch=2,
                                 pool_blocks=32)
    base = serve_greedy(params, cfg, config, prompts, n_new, max_steps=400)
    assert base.outputs == refs, f"{arch}: per-instance cluster vs oracle"
    assert base.kv_moved > 0, f"{arch}: expected mid-stream KV movement"

    mesh = make_mesh(mesh_shape, ("data", "model"))
    layout = ServeLayout(batch_axes=("data",), pool_axes=pool_axes)
    on_mesh, one_dev = mesh_matches_one_device(params, cfg, config, prompts,
                                               n_new, mesh, layout)
    assert one_dev.outputs == refs, f"{arch}: global pool (vmap) vs oracle"
    assert one_dev.kv_moved > 0 and on_mesh.kv_moved > 0
    assert one_dev.pool_copy_steps == 0, \
        f"{arch}: global-pool donation broken ({one_dev.pool_copy_steps})"
    print(f"OK {arch} n_inst={n_inst} pool_axes={pool_axes} "
          f"mesh={mesh_shape}")


if __name__ == "__main__":
    check("olmo-1b", 2, ("data",), (2, 1))          # 2 ranks / 2 shards
    check("olmo-1b", 4, ("data", "model"), (2, 2))  # 4 ranks / 2x2 mesh
    check("qwen2-moe-a2.7b", 2, ("data",), (2, 1))  # MoE + global pool
    print("ALL OK")
