"""Sharding rules (divisibility-aware logical axes) + dry-run HLO
collective parser."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.launch.dryrun import parse_collectives
from repro.launch.mesh import make_mesh
from repro.parallel.sharding import (batch_spec, set_rule_overrides,
                                     spec_for, tree_shardings)


@pytest.fixture(scope="module")
def mesh():
    # 1-device mesh with the production axis names (sizes 1 → rules drop)
    return make_mesh((1, 1), ("data", "model"),
                            devices=jax.devices()[:1])


class FakeMesh:
    """Duck-typed mesh with arbitrary axis sizes for rule unit tests."""

    def __init__(self, shape):
        self.shape = shape


def test_spec_divisibility():
    m = FakeMesh({"pod": 2, "data": 16, "model": 16})
    # divisible: sharded
    assert spec_for(m, ("vocab", "embed"), (152064, 1024)) == P("model", "data")
    # head dim 40 not divisible by 16: dropped
    assert spec_for(m, ("heads",), (40,)) == P(None)
    assert spec_for(m, ("heads",), (5120,)) == P("model")


def test_batch_spec_partial_axes():
    m = FakeMesh({"pod": 2, "data": 16, "model": 16})
    # batch 256 divisible by pod*data=32 → both axes
    assert batch_spec(m, (256, 4096)) == P(("pod", "data"), None)
    # batch 1 (long_500k): unsharded
    assert batch_spec(m, (1, 128)) == P(None, None)
    # batch 16: only a prefix that divides
    assert batch_spec(m, (2, 8)) == P(("pod",), None)


def test_no_duplicate_axes():
    m = FakeMesh({"data": 16, "model": 16})
    # two logical dims both mapping to model: second is dropped
    sp = spec_for(m, ("vocab", "ffn"), (4096, 4096))
    axes = [a for a in sp if a is not None]
    assert axes.count("model") == 1


def test_rule_overrides():
    m = FakeMesh({"data": 16, "model": 16})
    try:
        set_rule_overrides({"embed": None})
        assert spec_for(m, ("embed",), (1024,)) == P(None)
        set_rule_overrides({"embed": "model"})
        assert spec_for(m, ("embed",), (1024,)) == P("model")
    finally:
        set_rule_overrides(None)


def test_tree_shardings(mesh):
    abstract = {"w": jax.ShapeDtypeStruct((64, 32), jnp.float32)}
    axes = {"w": ("embed", "ffn")}
    sh = tree_shardings(mesh, abstract, axes)
    assert sh["w"].spec == P(None, None)  # 1-device mesh: all dropped


HLO_SAMPLE = """
  %all-gather.1 = f32[16,4096,1024]{2,1,0} all-gather(%fusion.1), channel_id=1, replica_groups={{0,1,2,3}}, dimensions={1}
  %all-reduce.7 = bf16[256,128]{1,0} all-reduce(%x), replica_groups=[16,16]<=[256], to_apply=%add
  %all-to-all.2 = f32[16,256,1,176]{3,2,1,0} all-to-all(%y), replica_groups={{0,8}}, dimensions={0}
  %add.5 = f32[4,4]{1,0} add(%a, %b)
  %collective-permute.3 = bf16[8,8]{1,0} collective-permute(%z), source_target_pairs={{0,1}}
"""


def test_parse_collectives_kinds_and_sizes():
    colls = parse_collectives(HLO_SAMPLE)
    kinds = sorted(c["kind"] for c in colls)
    assert kinds == ["all-gather", "all-reduce", "all-to-all",
                     "collective-permute"]
    ag = next(c for c in colls if c["kind"] == "all-gather")
    assert ag["operand_bytes"] == 16 * 4096 * 1024 * 4
    assert ag["group"] == 4
    ar = next(c for c in colls if c["kind"] == "all-reduce")
    assert ar["operand_bytes"] == 256 * 128 * 2
    assert ar["wire_bytes"] == 2 * ar["operand_bytes"]
    assert ar["group"] == 16


def test_parse_collectives_ignores_compute():
    assert parse_collectives("%m = f32[8,8] dot(%a, %b)") == []
