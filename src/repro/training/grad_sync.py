"""Wire-level compressed gradient sync across the pod (DCN) axis.

``grad_sync_compressed`` is a shard_map body: each pod holds its local
gradient; we quantize to int8 (+ fp32 scale), all_gather over the ``pod``
axis, and average after dequantization. DCN bytes drop 4x vs fp32 (2x vs
bf16); the int8 all-gather is visible in lowered HLO, which the multi-pod
dry-run and §Perf use to account the savings.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.training.compression import quantize_int8


def _sync_one(g, axis_name):
    q, s = quantize_int8(g)
    qs = jax.lax.all_gather(q, axis_name)            # [n_pods, ...] int8
    ss = jax.lax.all_gather(s, axis_name)            # [n_pods] f32
    n = qs.shape[0]
    deq = qs.astype(jnp.float32) * ss.reshape((n,) + (1,) * g.ndim)
    return jnp.mean(deq, axis=0).astype(g.dtype)


def grad_sync_compressed(grads, axis_name: str = "pod"):
    """shard_map body: int8 all-gather + local mean over ``axis_name``."""
    return jax.tree.map(lambda g: _sync_one(g, axis_name), grads)


def make_grad_sync(mesh, axis_name: str = "pod"):
    """jit-able compressed cross-pod gradient averaging.

    Gradients are assumed replicated within a pod (post data-axis psum)
    and DIFFERENT across pods; output is the pod-averaged gradient.
    """
    def spec_for(g):
        return P(axis_name, *([None] * (g.ndim)))    # stacked per pod

    def sync(stacked_grads):
        # stacked_grads: each leaf [n_pods, ...]; shard over pod axis.
        in_specs = jax.tree.map(lambda g: P(axis_name), stacked_grads)
        out_specs = in_specs

        def body(gl):
            return jax.tree.map(
                lambda g: _sync_one(g[0], axis_name)[None], gl)

        return jax.shard_map(body, mesh=mesh, in_specs=(in_specs,),
                             out_specs=out_specs)(stacked_grads)

    return jax.jit(sync)
