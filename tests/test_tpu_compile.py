"""The served path's kernels compile for a TPU v5e — described, not attached.

Each test lowers one kernel at the widths the chip smoke runs and asserts
that the compiled program holds the Pallas kernel (``tpu_custom_call``).
What the chip's compiler refuses (an unsupported layout, too much fast
memory) fails here, at no chip time.  The topology is described inside a
fixture, so only the worker that runs this file loads the TPU compiler.
"""
import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.core import spikes
from repro.pipeline.batch import device_shape
from repro.pipeline.builder import DEFAULT_BIN_SIZES

SMOKE_ROWS = 10_000          # concurrent jobs in the chip smoke
SMOKE_SAMPLES = 256          # committed samples per job per tick


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """Programs compiled for a described chip cannot be read back without
    one, so keep them out of the persistent cache."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


def _compile_device_call(one_chip, sizes, shape):
    from repro.kernels.ops import spike_hist_packed
    from repro.kernels.spike_hist import pack_fields
    fields = pack_fields([spikes.num_bins(c) for c in sizes])
    x = jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)
    _assert_kernel(spike_hist_packed.lower(x, fields=fields,
                                           interpret=False).compile())


@pytest.mark.parametrize("bins", [*DEFAULT_BIN_SIZES, "all"])
def test_engine_device_call_compiles(one_chip, bins):
    sizes = DEFAULT_BIN_SIZES if bins == "all" else (bins,)
    _compile_device_call(one_chip, sizes,
                         device_shape(SMOKE_ROWS, SMOKE_SAMPLES))


@pytest.mark.parametrize("rows", [256 << k for k in range(7)])
def test_engine_device_call_compiles_at_every_row_bucket(one_chip, rows):
    """Every padded shape the smoke's warm-up compiles, 256 to 16,384
    rows."""
    assert device_shape(rows, SMOKE_SAMPLES) == (rows, SMOKE_SAMPLES)
    _compile_device_call(one_chip, DEFAULT_BIN_SIZES, (rows, SMOKE_SAMPLES))


def test_spike_hist_pallas_compiles(one_chip):
    from repro.kernels.spike_hist import spike_hist_pallas
    x = jax.ShapeDtypeStruct((400_000,), jnp.float32, sharding=one_chip)
    _assert_kernel(jax.jit(lambda r: spike_hist_pallas(
        r, 15, interpret=False)).lower(x).compile())


def test_ema_scan_pallas_compiles(one_chip):
    from repro.kernels.ema_scan import ema_scan_pallas
    x = jax.ShapeDtypeStruct((4096,), jnp.float32, sharding=one_chip)
    _assert_kernel(jax.jit(lambda p: ema_scan_pallas(
        p, interpret=False)).lower(x).compile())
