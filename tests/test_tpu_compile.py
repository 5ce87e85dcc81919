"""Ahead-of-time compiles of the ``cim_mvm`` Pallas kernel for a TPU v5e.

The TPU compiler is installed with jaxlib, so a chip that is described
(not attached) can refuse a kernel here: layout, dtype and VMEM
refusals show up before any run on hardware.  Each case compiles the
tile-batched kernel with ``mode="compiled"`` at the crossbar shape of one
chip preset and checks that a Mosaic kernel (``tpu_custom_call``) is in
the compiled program.

The topology is described inside a module-scoped fixture: loading the
TPU library at import time would make parallel test workers contend
for its lock.
"""
import os

import pytest

from repro.core.abstraction import get_arch
from repro.kernels.cim_mvm import cim_mvm_params
from repro.kernels.cim_mvm.ops import _cim_mvm_tiles_impl

#: preset -> (tiles T, rows M, crossbar rows R, columns C); R and C are
#: the preset's crossbar size, so each case is one node's tile batch
SHAPES = {
    "toy": (8, 256, 32, 128),                 # dac 8, adc 8: bf16 planes
    "puma": (8, 256, 128, 128),               # dac 8, adc 1: bf16 planes
    "jia-issc21": (8, 256, 1152, 256),        # dac 1, cell 1: int8 planes
    "isaac-baseline": (8, 256, 128, 128),     # dac 1, cell 2: int8 planes
}


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:         # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("preset", sorted(SHAPES))
def test_cim_mvm_tiles_compiles_for_v5e(one_chip, preset):
    import jax
    import jax.numpy as jnp
    t, m, r, c = SHAPES[preset]
    params = cim_mvm_params(get_arch(preset))
    x = jax.ShapeDtypeStruct((t, m, r), jnp.int32, sharding=one_chip)
    w = jax.ShapeDtypeStruct((t, r, c), jnp.int32, sharding=one_chip)
    compiled = _cim_mvm_tiles_impl.lower(x, w, params=params,
                                         mode="compiled").compile()
    assert "tpu_custom_call" in compiled.as_text()
