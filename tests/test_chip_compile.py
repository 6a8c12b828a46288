"""The Pallas kernels compile for a TPU v5e at real widths.

Each test lowers one `kernels.ops` entry point with `interpret=False` for a
DESCRIBED v5e chip (no chip attached) at n = 2^20 rows and compiles it with
the TPU compiler, which refuses what interpret mode accepts: block shapes
off the (8, 128) tiling, unaligned slices, lane-splitting reshapes, VMEM
overruns.  The topology is described inside a fixture, never while a
module is imported, so only the test worker that runs this file loads the
TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

N = 1 << 20


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    # requirements.txt pins a libtpu that describes a v5e; failing to do
    # so is a broken installation, not a reason to skip
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip; keep it out."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compile_has_kernel(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_feature_tables_compiles_for_v5e(one_chip, no_persistent_cache):
    m, B, W = 28, 256, 65
    _compile_has_kernel(
        lambda b, lf, w, y: ops.feature_tables(
            b, lf, w, y, B=B, W=W, num_classes=2, interpret=False),
        _spec(one_chip, (m, N), jnp.uint8), _spec(one_chip, (N,), jnp.int32),
        _spec(one_chip, (N,), jnp.float32), _spec(one_chip, (N,), jnp.int32))


def test_categorical_tables_compiles_for_v5e(one_chip, no_persistent_cache):
    m, V, Lp = 4, 1024, 64
    _compile_has_kernel(
        lambda x, lf, w, y: ops.categorical_tables(
            x, lf, w, y, V=V, Lp=Lp, num_classes=2, interpret=False),
        _spec(one_chip, (m, N), jnp.int32), _spec(one_chip, (N,), jnp.int32),
        _spec(one_chip, (N,), jnp.float32), _spec(one_chip, (N,), jnp.int32))


def test_split_scan_compiles_for_v5e(one_chip, no_persistent_cache):
    m, Lp = 8, 16
    _compile_has_kernel(
        lambda v, si, lf, w, y, c: ops.split_scan_supersplit(
            v, si, lf, w, y, c, Lp, num_classes=2, interpret=False),
        _spec(one_chip, (m, N), jnp.float32),
        _spec(one_chip, (m, N), jnp.int32), _spec(one_chip, (N,), jnp.int32),
        _spec(one_chip, (N,), jnp.float32), _spec(one_chip, (N,), jnp.int32),
        _spec(one_chip, (m, Lp + 1), jnp.bool_))
