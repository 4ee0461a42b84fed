import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=8")
"""Subprocess helper: the launch layer must lower+compile one cell of
every kind on a small (2,2,2) pod mesh, and the compressed cross-pod
grad sync must be numerically exact up to int8 quantization."""
import sys

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..",
                                "src"))

from repro.launch.inputs import build_cell           # noqa: E402
from repro.launch.mesh import make_mesh              # noqa: E402
from repro.launch.roofline import collective_bytes_from_hlo  # noqa: E402


def check_cells():
    mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
    cells = [("qwen3-0.6b", "train_4k"), ("qwen3-0.6b", "prefill_32k"),
             ("qwen3-0.6b", "decode_32k"), ("xlstm-350m", "decode_32k"),
             ("recurrentgemma-9b", "prefill_32k")]
    for arch, shape in cells:
        cell = build_cell(arch, shape, mesh)
        names = list(cell.kwargs)
        jitted = jax.jit(lambda *a: cell.fn(**dict(zip(names, a))),
                         in_shardings=tuple(cell.in_shardings.get(n)
                                            for n in names),
                         out_shardings=cell.out_shardings)
        with jax.set_mesh(mesh):
            compiled = jitted.lower(
                *[cell.kwargs[n] for n in names]).compile()
        coll = collective_bytes_from_hlo(compiled.as_text())
        assert compiled.cost_analysis() is not None
        print(f"OK cell {arch} x {shape} (multi-pod mini mesh) "
              f"coll={sum(coll.values())}")


def _shard_map(f, mesh, in_specs, out_specs):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def check_grad_sync():
    from repro.training.grad_sync import _sync_one
    mesh = make_mesh((4,), ("pod",))
    g = np.random.default_rng(0).normal(size=(4, 32, 16)).astype(np.float32)

    fn = jax.jit(_shard_map(
        lambda x: _sync_one(x[0], "pod")[None],
        mesh=mesh, in_specs=P("pod"), out_specs=P("pod")))
    with jax.set_mesh(mesh):
        out = np.asarray(fn(jnp.asarray(g)))
    want = g.mean(axis=0)
    for i in range(4):
        np.testing.assert_allclose(out[i], want, atol=2e-2)
    # int8 all-gather must appear in the lowered HLO (wire-level claim).
    with jax.set_mesh(mesh):
        txt = jax.jit(_shard_map(
            lambda x: _sync_one(x[0], "pod")[None], mesh=mesh,
            in_specs=P("pod"), out_specs=P("pod"))
            ).lower(jnp.asarray(g)).compile().as_text()
    assert "s8[" in txt and "all-gather" in txt
    print("OK grad_sync int8 wire format + numerics")


if __name__ == "__main__":
    check_cells()
    check_grad_sync()
    print("ALL OK")
