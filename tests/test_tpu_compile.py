"""The main path's device programs compile for a TPU v5e at real sizes.

Nothing runs: the chip is described, not attached, and the TPU
compiler refuses here what it would refuse on the chip (VMEM overflow,
misaligned tiles).  The topology is described inside a fixture, never
at import, and all such compiles stay in this one file.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import stream_ops
from repro.kernels.ops import flash_attention


@pytest.fixture(scope="module")
def topo():
    # skip only where the TPU compiler is absent; any other failure to
    # describe the chip is a failure of these tests
    pytest.importorskip("libtpu")
    from jax.experimental import topologies
    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _columns(n, sharding):
    return [jax.ShapeDtypeStruct((n,), jnp.uint32, sharding=sharding)] * 4


@pytest.mark.parametrize("n", [128, 1024, 1 << 20])
def test_fid_slots_twin_compiles(one_chip, n):
    compiled = stream_ops._fid_slots_jit.lower(
        *_columns(n, one_chip), n_slots=64).compile()
    # the name scope reaches the TPU program's op metadata
    assert 'op_name="jit(_fid_slots_jit)/fid_slots/' in compiled.as_text()


@pytest.mark.parametrize("n", [1000, 262_144, 1 << 20])
def test_tiled_fid_slots_pallas_compiles(one_chip, n):
    compiled = stream_ops._fid_slots_tiled.lower(
        *_columns(n, one_chip), n_slots=64).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_flash_attention_compiles_at_granite_moe_shape(one_chip):
    """granite-moe-1b-a400m attention: 16 q heads, 8 kv heads, head
    dim 64, at a 2048-token sequence in bf16."""
    B, S, H, KV, D = 1, 2048, 16, 8, 64
    q = jax.ShapeDtypeStruct((B, S, H, D), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((B, S, KV, D), jnp.bfloat16, sharding=one_chip)
    compiled = flash_attention.lower(q, kv, kv).compile()
    assert "tpu_custom_call" in compiled.as_text()
