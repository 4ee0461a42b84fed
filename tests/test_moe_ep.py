"""EP-grouped MoE dispatch == ungrouped dispatch (numerical equivalence).

The 2D expert-parallel formulation (§Perf-3) is what the production
train cells lower; it must compute the same function as the plain
dispatch when capacity is no-drop. (With drops the two differ only in
WHICH overflow tokens drop — per-group vs global capacity.)
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="property tests need hypothesis "
                    "(pip install -r requirements-dev.txt)")
from hypothesis import given, settings, strategies as st

from repro.configs import get_smoke_config
from repro.launch.mesh import make_mesh
from repro.models.model import init_params
from repro.models.moe import apply_moe


def _mesh11():
    return jax.set_mesh(make_mesh((1, 1), ("data", "model")))


def _grouped(lp, x, cfg, ep_groups):
    """No-drop EP-grouped MoE, jitted: its bare-spec sharding pins
    resolve against the mesh set in context only inside a trace."""
    return jax.jit(lambda lp, x: apply_moe(lp, x, cfg, capacity_factor=-1.0,
                                           ep_groups=ep_groups))(lp, x)


@pytest.mark.parametrize("arch", ["qwen2-moe-a2.7b", "kimi-k2-1t-a32b"])
def test_ep_grouped_equals_plain(arch):
    cfg = get_smoke_config(arch)
    key = jax.random.PRNGKey(0)
    params = init_params(key, cfg)
    lp = jax.tree.map(lambda a: a[0], params["moe_layers"])["moe"]
    x = jax.random.normal(key, (4, 8, cfg.d_model),
                          jnp.float32).astype(cfg.dtype)
    with _mesh11():
        plain = apply_moe(lp, x, cfg, capacity_factor=-1.0, ep_groups=0)
        grouped = _grouped(lp, x, cfg, ep_groups=4)
    np.testing.assert_allclose(np.asarray(plain, np.float32),
                               np.asarray(grouped, np.float32),
                               atol=3e-2, rtol=3e-2)


@settings(max_examples=10, deadline=None)
@given(groups=st.sampled_from([2, 4, 8]), seed=st.integers(0, 2**31 - 1))
def test_ep_grouped_equivalence_property(groups, seed):
    cfg = get_smoke_config("qwen2-moe-a2.7b")
    key = jax.random.PRNGKey(seed)
    params = init_params(jax.random.PRNGKey(0), cfg)
    lp = jax.tree.map(lambda a: a[0], params["moe_layers"])["moe"]
    x = jax.random.normal(key, (groups, 8, cfg.d_model),
                          jnp.float32).astype(cfg.dtype)
    with _mesh11():
        plain = apply_moe(lp, x, cfg, capacity_factor=-1.0, ep_groups=0)
        grouped = _grouped(lp, x, cfg, ep_groups=groups)
    np.testing.assert_allclose(np.asarray(plain, np.float32),
                               np.asarray(grouped, np.float32),
                               atol=3e-2, rtol=3e-2)
