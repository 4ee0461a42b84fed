"""Every architecture module keeps the contract of ``archs/__init__.py``,
and the shared harness reaches the model only through it."""
import dataclasses
import glob
import os
import re

import pytest

from harness.spec import ARCH_DIR, BENCH_DIR, load_arch, load_benchmark, \
    load_cell

CONTRACT = ("spec", "program_config", "program_params", "gaps",
            "param_count", "kv_bytes_per_token", "token_flops",
            "prompt_flops", "decode_attn_work", "prefill_attn_work")
ARCHS = sorted(os.path.basename(p)[:-3]
               for p in glob.glob(os.path.join(ARCH_DIR, "*.py"))
               if not p.endswith("__init__.py"))
CELLS = [w["name"] for w in load_benchmark()["workloads"]]


@pytest.mark.parametrize("name", ARCHS)
def test_module_keeps_the_contract(name):
    arch = load_arch(name)
    assert load_arch(name) is arch
    for fn in CONTRACT:
        assert callable(getattr(arch, fn, None)), fn


@pytest.mark.parametrize("name", CELLS)
def test_cell_spec_is_frozen_and_hashable(name):
    cell = load_cell(name)
    m = cell.model
    assert cell.model is m and type(m).__module__ == cell.arch.__name__
    assert dataclasses.is_dataclass(m) and hash(m) == hash(
        cell.arch.spec(cell.config))
    with pytest.raises(dataclasses.FrozenInstanceError):
        m.layers = 1
    for field in ("name", "layers", "d_model", "vocab", "dtype"):
        assert getattr(m, field) is not None, field


def test_harness_names_no_architecture_field():
    """Only the architecture modules read the model's own sizes and
    weight names; the harness uses the contract's fields."""
    dense_only = re.compile(r"\b(kv_heads|head_dim|d_ff|wq|wk|wv|w_gate|"
                            r"num_key_value_heads|intermediate_size)\b")
    for path in glob.glob(os.path.join(BENCH_DIR, "harness", "*.py")):
        with open(path) as f:
            hits = dense_only.findall(f.read())
        assert not hits, (os.path.basename(path), hits)
