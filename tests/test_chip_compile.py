"""Compile-only checks of the scheduler's device kernels for a TPU v5e chip.

Each case lowers and compiles one kernel of the main path for a described
(not attached) ``v5e:2x2`` topology, on one of its chips, so the chip's own
compiler refuses here what it would refuse on the chip: unsupported
operand types or reductions, tiling or memory limits. Nothing runs;
results and times come from ``chip_smoke.py`` on the chip.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU compiler library, so describing it
while test modules are collected would fail on every other worker.
"""

import os

import jax
import jax.numpy as jnp
import pytest

# Shapes of the rehearsal: a few seconds per compile. The smoke's real
# sweep shape (B=16384, T=478, m=180) compiles the same programs.
B, T, M, N_COMP = 4096, 512, 96, 3
SIM_B = 1024


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    with pytest.MonkeyPatch.context() as mp:
        # Keep the compiler's logs out of the temp dir.
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            topo = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # A program compiled for a described chip is written to a persistent
        # cache but cannot be read back without one; keep the cache off.
        was_enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        yield SingleDeviceSharding(topo.devices[0])
        jax.config.update("jax_enable_compilation_cache", was_enabled)
        compilation_cache.reset_cache()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize(
    "per_row,with_resources", [(False, False), (True, True), (False, True)],
    ids=["shared", "per_row_resources", "shared_resources"],
)
def test_msr_kernel_compiles_f64(one_chip, per_row, with_resources):
    """The XLA contraction that TPU sweeps run, in (emulated) float64."""
    from repro.core.sim_jax import _msr_kernel

    task_map = (B, T) if per_row else (T,)
    with jax.enable_x64(True):
        specs = [
            _spec(one_chip, (B, T), jnp.int64),
            _spec(one_chip, task_map, jnp.int64),
            _spec(one_chip, task_map, jnp.float64),
            _spec(one_chip, (N_COMP, M), jnp.float64),
            _spec(one_chip, (N_COMP, M), jnp.float64),
            _spec(one_chip, (M,), jnp.float64),
        ]
        if with_resources:
            specs += _resource_specs(one_chip)
        kernel = _msr_kernel(per_row=per_row, with_resources=with_resources)
        compiled = kernel.lower(*specs).compile()
    assert "f64" in compiled.as_text()


def _resource_specs(sharding):
    """Memory per component and per machine, then the network tables."""
    return [
        _spec(sharding, (N_COMP,), jnp.float64),
        _spec(sharding, (M,), jnp.float64),
        _spec(sharding, (M, M), jnp.float64),
        _spec(sharding, (N_COMP, N_COMP), jnp.float64),
        _spec(sharding, (N_COMP,), jnp.float64),
        _spec(sharding, (N_COMP,), jnp.float64),
        _spec(sharding, (), jnp.float64),
    ]


def test_edit_kernel_compiles_f64(one_chip):
    """``msr_edits``: refine's relocate and swap grids of one base row,
    scored in (emulated) float64."""
    from repro.core.sim_jax import _msr_kernel

    with jax.enable_x64(True):
        compiled = _msr_kernel(edits=True).lower(*_edit_specs(one_chip)).compile()
    assert "f64" in compiled.as_text()


def test_resource_edit_kernel_compiles_f64(one_chip):
    """``msr_edits_resources``: the same grids on a cluster with network and
    memory resources, every candidate scored on every machine."""
    from repro.core.sim_jax import _msr_kernel

    with jax.enable_x64(True):
        specs = _edit_specs(one_chip) + _resource_specs(one_chip)
        compiled = _msr_kernel(edits=True, with_resources=True).lower(*specs).compile()
    assert "f64" in compiled.as_text()


def _edit_specs(sharding):
    return [
        _spec(sharding, (T,), jnp.int32),
        _spec(sharding, (T,), jnp.int32),
        _spec(sharding, (T,), jnp.int32),
        _spec(sharding, (T,), jnp.float64),
        _spec(sharding, (N_COMP, M), jnp.float64),
        _spec(sharding, (N_COMP, M), jnp.float64),
        _spec(sharding, (M,), jnp.float64),
    ]


@pytest.mark.parametrize("drop", [False, True], ids=["grow", "drop"])
@pytest.mark.parametrize("with_resources", [False, True], ids=["scalar", "resources"])
def test_count_edit_kernels_compile_f64(one_chip, with_resources, drop):
    """``msr_count_edits`` (``_resources``): refine's growth steps and drops
    of k base rows, scored in (emulated) float64."""
    from repro.core.sim_jax import _msr_kernel

    k = N_COMP * N_COMP
    with jax.enable_x64(True):
        specs = [
            _spec(one_chip, (k, T), jnp.int32),
            _spec(one_chip, (k, N_COMP), jnp.int32),
            _spec(one_chip, (k, N_COMP), jnp.float64),
            _spec(one_chip, (k,), jnp.int32),
            *_edit_specs(one_chip)[4:],
        ]
        if with_resources:
            specs += _resource_specs(one_chip)
        kernel = _msr_kernel(count_edits=True, with_resources=with_resources)
        compiled = kernel.lower(*specs, drop=drop).compile()
    assert "f64" in compiled.as_text()


def test_simulate_fixed_point_compiles_f64(one_chip):
    """The ``simulate_batch`` while-loop fixed point, in float64."""
    from repro.core import linear_topology, paper_cluster, schedule
    from repro.core.sim_jax import _compiled_kernel, _static_descriptor

    cluster = paper_cluster((10, 10, 10))
    etg = schedule(linear_topology(), cluster, r0=1.0, rate_epsilon=1.0).etg
    n, m, t = etg.utg.n_components, cluster.n_machines, etg.total_tasks
    kernel = _compiled_kernel(_static_descriptor(etg))
    with jax.enable_x64(True):
        compiled = kernel.lower(
            _spec(one_chip, (SIM_B, t), jnp.int64),
            _spec(one_chip, (t,), jnp.int64),
            _spec(one_chip, (n,), jnp.float64),
            _spec(one_chip, (n, m), jnp.float64),
            _spec(one_chip, (n, m), jnp.float64),
            _spec(one_chip, (m,), jnp.float64),
            _spec(one_chip, (SIM_B,), jnp.float64),
        ).compile()
    assert "while" in compiled.as_text()
