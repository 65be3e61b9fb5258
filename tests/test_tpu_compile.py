"""Compile the delegation kernels for a TPU v5e chip that is described, not
attached.

Interpret mode (every other kernel test) cannot see what the chip's
compiler refuses: block shapes off the (8, 128) tiling, relayouts Mosaic
cannot lower, more VMEM than a kernel may use.  These tests compile the
channel pack and the trustee serve with ``interpret=False`` at the widths
``chip_smoke.py`` runs (16,384 rows, a 1,000,000-line table, W=4) and
check that the compiled program holds the Mosaic kernels.  Nothing runs,
so they say nothing about results or times.

The topology is described in a fixture, never at import: only one process
may load the TPU library, and every pytest worker imports every file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.delegation_pack import delegation_pack
from repro.kernels.delegation_serve import delegation_serve, num_row_tiles

ROWS, KEYS, WIDTH = 16_384, 1_000_000, 4
# the KV round's wire planes: key as two 16-bit planes, value, expect, op
PLANES = 2 + 2 * WIDTH + 1


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2, with the persistent compilation
    cache off: what is compiled for a described chip cannot be read back
    without one."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                                   # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _compiled_text(fn, shapes, sharding) -> str:
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("n_trustees,capacity", [(1, ROWS), (4, ROWS // 2)])
def test_pack_compiles_for_v5e(one_chip, n_trustees, capacity):
    text = _compiled_text(
        lambda d, p: delegation_pack(d, p, n_trustees=n_trustees,
                                     capacity=capacity, interpret=False),
        [((ROWS,), jnp.int32), ((ROWS, PLANES), jnp.float32)], one_chip)
    assert text.count("tpu_custom_call") == 1


def test_serve_compiles_for_v5e(one_chip):
    """The serve's four grid kernels: PUT commit, ADD scatter, response
    gather, CAS commit."""
    rows, table = ((ROWS,), jnp.int32), ((ROWS, WIDTH), jnp.float32)
    text = _compiled_text(
        lambda *a: delegation_serve(*a, interpret=False),
        [((KEYS, WIDTH), jnp.float32), rows, rows, table, table, rows,
         ((num_row_tiles(ROWS, 256),), jnp.bool_)], one_chip)
    assert text.count("tpu_custom_call") == 4
