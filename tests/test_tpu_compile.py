"""The serving path's Pallas kernels compile for a TPU v5e, natively.

Interpret mode (every other kernel test) cannot see what the TPU
compiler refuses: block shapes Mosaic cannot tile, more VMEM than a
kernel may use. These cases compile each kernel with ``interpret=False``
at published widths for a v5e that is described, not attached, and
assert that the program carries the kernel (``tpu_custom_call``).
Nothing runs. The topology is described inside a module fixture, never
at import: only one process at a time may load the TPU library, and
every test worker imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

BS = 16            # ServingConfig.v5e() block size
NB = 1024          # blocks in one instance's pool (chip_smoke.py)
MB = 512           # table width: 8192 local tokens / BS


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _spec(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(lowered):
    text = lowered.compile().as_text()
    assert "tpu_custom_call" in text, "the Pallas kernel is not in the program"


@pytest.mark.parametrize("H,K,hd,ranks,R,nb,mb", [
    pytest.param(16, 16, 128, 0, 16, NB, MB,         # olmo-1b (MHA)
                 id="16-16-128-0"),
    pytest.param(32, 8, 128, 0, 16, NB, MB,          # mistral-nemo (GQA)
                 id="32-8-128-0"),
    pytest.param(64, 8, 112, 0, 16, NB, MB,          # kimi-k2 head dim
                 id="64-8-112-0"),
    pytest.param(16, 16, 128, 4, 16, NB, MB,         # 4 stacked rank pools
                 id="16-16-128-4"),
    # The benchmark cells' decode calls: nemo12b-longdecode's instance
    # pool and table bucket, olmo1b-chat's batch, pool and bucket.
    pytest.param(32, 8, 128, 0, 16, 3072, 1024, id="nemo12b-longdecode"),
    pytest.param(16, 16, 128, 0, 32, 2048, 128, id="olmo1b-chat"),
])
def test_paged_decode_compiles(one_chip, monkeypatch, H, K, hd, ranks, R,
                               nb, mb):
    q = _spec(one_chip, (R, H, hd))
    if not ranks:
        pool = _spec(one_chip, (nb, BS, K, hd))
        lowered = ops.paged_micro_attention.lower(
            q, pool, pool, _spec(one_chip, (R, mb), jnp.int32),
            _spec(one_chip, (R,), jnp.int32), backend="pallas",
            interpret=False)
    else:
        # The global-pool path resolves interpret from the process's
        # backend; this process is on the CPU, the program is for a TPU.
        monkeypatch.setattr(ops, "_on_tpu", lambda: True)
        pools = _spec(one_chip, (ranks, nb // ranks, BS, K, hd))
        lowered = jax.jit(ops.paged_micro_attention_ranks).lower(
            q, pools, pools, _spec(one_chip, (ranks, R, mb), jnp.int32),
            _spec(one_chip, (ranks, R), jnp.int32))
    _assert_kernel(lowered)


@pytest.mark.parametrize("C", [256, 512])
def test_paged_prefill_compiles(one_chip, C):
    H = K = 16                # olmo-1b
    hd = 128
    pool = _spec(one_chip, (NB, BS, K, hd))
    lowered = ops.paged_prefill_attention.lower(
        _spec(one_chip, (C, H, hd)), pool, pool,
        _spec(one_chip, (MB,), jnp.int32), _spec(one_chip, (), jnp.int32),
        backend="pallas", interpret=False)
    _assert_kernel(lowered)


@pytest.mark.parametrize("B,S,H,K,hd", [
    (1, 2048, 16, 16, 128),   # olmo-1b
])
def test_flash_prefill_compiles(one_chip, B, S, H, K, hd):
    kv = _spec(one_chip, (B, S, K, hd))
    lowered = ops.flash_prefill.lower(_spec(one_chip, (B, S, H, hd)), kv, kv,
                                      interpret=False)
    _assert_kernel(lowered)
