"""Ahead-of-time compiles of the scheduling path for a TPU v5e chip.

The TPU compiler is installed alongside JAX, so the scheduling kernels
compile here for a described (not attached) v5e chip, with
``interpret=False``: this catches what interpret mode cannot — unaligned
lane slices, scoped-VMEM overflows — without a chip. Nothing runs, so
these tests say nothing about results or speed.

The topology is described inside a module-scoped fixture, never at import
(only one process may hold the TPU library; under pytest-xdist every
worker imports this file, and only the worker running it may load the
library). The persistent compile cache is off around the compiles: an
executable compiled for a described chip cannot be read back without one.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.conflict.conflict import (
    conflict_block_pallas,
    conflict_matrix_pallas,
)
from repro.kernels.levels.levels import MAX_WINDOW, wave_levels_pallas

#: SIS footprint widths on the smoke network: max degree 9 -> 10 reads
NR, NW = 10, 1
WINDOWS = (64, 256, 4096)


@pytest.fixture(scope="module")
def no_compile_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def topo(no_compile_cache):
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("w", WINDOWS)
def test_conflict_matrix_compiles(one_chip, w):
    s = lambda shape, dt: _spec(one_chip, shape, dt)
    compiled = conflict_matrix_pallas.lower(
        s((w, NR), jnp.int32), s((w, NW), jnp.int32), s((w,), jnp.bool_),
        strict=True, interpret=False).compile()
    _assert_kernel(compiled)


@pytest.mark.parametrize("w", WINDOWS)
def test_conflict_block_compiles(one_chip, w):
    s = lambda shape, dt: _spec(one_chip, shape, dt)
    compiled = conflict_block_pallas.lower(
        s((w, NR), jnp.int32), s((w, NW), jnp.int32),
        s((w, NR), jnp.int32), s((w, NW), jnp.int32),
        s((w,), jnp.bool_), s((w,), jnp.bool_),
        strict=True, interpret=False).compile()
    _assert_kernel(compiled)


@pytest.mark.parametrize("w", (64, 100, 256, 4096))
def test_wave_levels_compiles(one_chip, w):
    """W < 128 and W not a multiple of 128 pad to whole 128-lane blocks."""
    s = lambda shape, dt: _spec(one_chip, shape, dt)
    compiled = wave_levels_pallas.lower(
        s((w, w), jnp.bool_), s((w,), jnp.bool_),
        interpret=False).compile()
    _assert_kernel(compiled)


@pytest.mark.parametrize("w", WINDOWS)
def test_wave_levels_floored_compiles(one_chip, w):
    """The overlapped engines' call: levels under a carry-frontier floor."""
    s = lambda shape, dt: _spec(one_chip, shape, dt)
    compiled = wave_levels_pallas.lower(
        s((w, w), jnp.bool_), s((w,), jnp.bool_), s((w,), jnp.int32),
        interpret=False).compile()
    _assert_kernel(compiled)


def test_wave_levels_refuses_window_past_vmem():
    w = MAX_WINDOW + 128
    with pytest.raises(ValueError, match="scoped VMEM"):
        wave_levels_pallas.lower(jax.ShapeDtypeStruct((w, w), jnp.bool_),
                                 jax.ShapeDtypeStruct((w,), jnp.bool_),
                                 interpret=False)


@pytest.fixture(scope="module")
def sis_net():
    """SIS's 10^6-agent Watts-Strogatz network (max degree 9)."""
    from repro.topology import connect_isolated, watts_strogatz

    net = connect_isolated(watts_strogatz(10**6, 4, 0.1, jax.random.key(0)),
                           jax.random.key(1))
    assert net.max_degree + 1 == NR
    return net


def _sis_engine(net, monkeypatch):
    """The wavefront engine for SIS on ``net``, steered onto the compiled
    Pallas kernels as on a chip (this process's backend is the CPU)."""
    import repro.kernels as kernels
    from repro.engine import make_engine
    from repro.mabs.sis import SISModel

    monkeypatch.setattr(kernels, "on_tpu", lambda: True)
    kernels.RESOLVED.clear()
    return make_engine("wavefront", SISModel(net), window=256)


def _assert_schedule_kernels(compiled):
    import repro.kernels as kernels

    assert kernels.RESOLVED == {"conflict_matrix": "pallas",
                                "wave_levels": "pallas"}
    assert compiled.as_text().count('custom_call_target="tpu_custom_call"') == 2


def test_sis_schedule_compiles_at_million_agents(one_chip, sis_net,
                                                 monkeypatch):
    """The wavefront engine's jitted window schedule for SIS on a
    10^6-agent Watts-Strogatz network."""
    eng = _sis_engine(sis_net, monkeypatch)
    key = jax.eval_shape(lambda: jax.random.key(0))
    i32 = _spec(one_chip, (), jnp.int32)
    compiled = eng._schedule.lower(
        _spec(one_chip, key.shape, key.dtype), i32, i32).compile()
    _assert_schedule_kernels(compiled)


def test_sis_device_loop_compiles_at_million_agents(one_chip, sis_net,
                                                    monkeypatch):
    """The barrier engine's whole-run device loop (every window scheduled
    and executed inside one ``while``) for SIS on the same network: both
    scheduling kernels compile inside the loop body."""
    eng = _sis_engine(sis_net, monkeypatch)
    state = {"states": _spec(one_chip, (sis_net.n_nodes,), jnp.int8)}
    compiled = eng._run_windows.lower(
        state, _spec(one_chip, (), jnp.uint32),
        _spec(one_chip, (), jnp.int32)).compile()
    _assert_schedule_kernels(compiled)
