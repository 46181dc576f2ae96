"""The substrate's Pallas kernels compile for a TPU v5e.

Interpret mode (every other kernel test) never runs Mosaic, so a kernel
that the chip's compiler refuses still passes there.  These tests compile
``batched_evict`` (f32 and i32 keys), ``fifo_grant`` and ``wake_solve``
for a described ``v5e:2x2`` topology, with nothing attached, at the SF30
TPC-H pool (P = 8,832 pages) and at a ragged pool (3 blocks + 129), the
grant also at the micro cells' pool (P = 3,712) alone and under a
four-lane ``jax.vmap``, the wake solve under that ``vmap`` at both cells'
pools (P = 3,712 and 14,336), and check that the compiled program holds the
Mosaic kernel under the name its ``pallas_call`` pins (``kernel_<op>``),
the name the benchmark's trace reduction matches.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, so describing it while the
module is collected would break every other test worker.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.pbm_timeline import (
    batched_evict_kernel,
    fifo_grant_kernel,
    wake_solve_kernel,
)

#: the SF30 TPC-H pool (make_tpch_db(scale=1.0), 8 streams, seed 7) and a
#: pool that leaves a ragged last page block
POOLS = [8832, 3 * 512 + 129]
#: the benchmark cells' pools (micro_lineitem_sf30: 3,635 pages, padded;
#: tpch_sf30: 14,336 padded)
CELL_POOL = 3712
TPCH_CELL_POOL = 14336


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off around it
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", before)
        compilation_cache.reset_cache()


def _compile_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("P", POOLS)
@pytest.mark.parametrize("key_dtype", [jnp.float32, jnp.int32])
def test_batched_evict_compiles(one_chip, P, key_dtype):
    text = _compile_text(
        batched_evict_kernel,
        _spec(one_chip, (P,), key_dtype),
        _spec(one_chip, (P,), jnp.float32),
        _spec(one_chip, (P,), jnp.bool_),
        _spec(one_chip, (), jnp.float32),
    )
    assert "tpu_custom_call" in text
    assert "%kernel_batched_evict." in text


@pytest.mark.parametrize(
    "P, lanes", [(P, None) for P in POOLS] + [(CELL_POOL, None),
                                              (CELL_POOL, 4)],
    ids=[str(P) for P in POOLS] + [str(CELL_POOL), f"4x{CELL_POOL}"])
def test_fifo_grant_compiles(one_chip, P, lanes):
    """The grant also compiles at the benchmark cells' pool, alone and
    batched over four lanes as the simulator's ``jax.vmap`` calls it."""
    batch = () if lanes is None else (lanes,)
    fn = fifo_grant_kernel if lanes is None else jax.vmap(fifo_grant_kernel)
    text = _compile_text(
        fn,
        _spec(one_chip, batch + (P,), jnp.int32),
        _spec(one_chip, batch + (P,), jnp.float32),
        _spec(one_chip, batch, jnp.float32),
        _spec(one_chip, batch, jnp.int32),
    )
    assert "tpu_custom_call" in text
    if lanes is None:
        assert "%kernel_fifo_grant." in text
    else:
        # under vmap the instruction is named vmap_kernel_fifo_grant_
        calls = [line for line in text.splitlines()
                 if "custom_call_target=\"tpu_custom_call\"" in line]
        assert len(calls) == 1 and "kernel_fifo_grant" in calls[0]


@pytest.mark.parametrize(
    "P, lanes", [(P, None) for P in POOLS] + [(CELL_POOL, 4),
                                              (TPCH_CELL_POOL, 4)],
    ids=[str(P) for P in POOLS] + [f"4x{CELL_POOL}", f"4x{TPCH_CELL_POOL}"])
def test_wake_solve_compiles(one_chip, P, lanes):
    """The solve also compiles batched over four lanes, as the
    simulator's ``jax.vmap`` calls it, at both benchmark pools: one
    Mosaic kernel for all four lanes."""
    batch = () if lanes is None else (lanes,)
    fn = wake_solve_kernel if lanes is None else jax.vmap(wake_solve_kernel)
    text = _compile_text(
        fn,
        _spec(one_chip, batch + (P,), jnp.int32),
        _spec(one_chip, batch + (P,), jnp.float32),
        _spec(one_chip, batch, jnp.float32),
        _spec(one_chip, batch, jnp.float32),
        _spec(one_chip, batch, jnp.int32),
    )
    assert "tpu_custom_call" in text
    if lanes is None:
        assert "%kernel_wake_solve." in text
    else:
        calls = [line for line in text.splitlines()
                 if "custom_call_target=\"tpu_custom_call\"" in line]
        assert len(calls) == 1 and "kernel_wake_solve" in calls[0]
