"""Ahead-of-time compiles of the main paths for a described TPU v5e.

Nothing runs here: each test lowers a program for a ``v5e:2x2`` topology
that the installed TPU compiler can describe without a chip, compiles it,
and checks what only that compiler can refuse — the Mosaic lowering of the
Pallas kernels (a kernel that compiles shows up as ``tpu_custom_call``),
their VMEM, and the program's fit in one chip's 16 GiB of HBM
(``memory_analysis``). The interpret-mode tests cannot see any of this.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and with several pytest workers only
the one given this file does.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding

from repro.core import sampler
from repro.distributed import ising as dising
from repro.kernels import checkerboard as kern
from repro.kernels import ops as kops

HBM_BYTES = 16 * 2 ** 30           # one v5e chip
TABLE1_SIDE = 20480                # paper Table 1, ising-160x128
BS = 128


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of these tests.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture
def mosaic(monkeypatch):
    """This process's backend is the CPU, where the kernels would take
    their interpreter branch; compile them through Mosaic instead."""
    monkeypatch.setattr(kern, "interpret_mode", lambda: False)


def _device_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


@pytest.mark.parametrize("side", [1024, TABLE1_SIDE])
@pytest.mark.parametrize("rule", ["metropolis_lut", "heat_bath"])
@pytest.mark.parametrize("backend", ["pallas", "pallas_lines"])
def test_kernel_compiles_for_v5e(topo, mosaic, backend, rule, side):
    one = SingleDeviceSharding(topo.devices[0])
    m = side // 2 // BS
    qb = jax.ShapeDtypeStruct((4, m, m, BS, BS), jnp.bfloat16, sharding=one)
    bits = jax.ShapeDtypeStruct((2, m, m, BS, BS), jnp.uint32, sharding=one)
    for color in (0, 1):
        step = jax.jit(lambda q, b: kops.update_color(
            q, b, 0.44, color, backend=backend, rule=rule))
        compiled = step.lower(qb, bits).compile()
        assert "tpu_custom_call" in compiled.as_text()
        assert _device_bytes(compiled) < HBM_BYTES


@pytest.mark.parametrize("backend,name", [
    ("pallas", "checkerboard_update"),
    ("pallas_lines", "checkerboard_update_lines")])
def test_kernels_keep_their_names(topo, mosaic, backend, name):
    """Each kernel's custom call carries its ``pallas_call`` name, which is
    what a trace shows for it."""
    one = SingleDeviceSharding(topo.devices[0])
    m = 1024 // 2 // BS
    qb = jax.ShapeDtypeStruct((4, m, m, BS, BS), jnp.bfloat16, sharding=one)
    bits = jax.ShapeDtypeStruct((2, m, m, BS, BS), jnp.uint32, sharding=one)
    step = jax.jit(lambda q, b: kops.update_color(q, b, 0.44, 0,
                                                  backend=backend))
    text = step.lower(qb, bits).compile().as_text()
    assert re.search(rf"%{name}(\.\d+)? = .* custom-call\(", text)


def test_table1_paper_chain_fits_one_chip(topo):
    """The measured XLA chain at the Table-1 lattice fits one chip."""
    one = SingleDeviceSharding(topo.devices[0])
    h = TABLE1_SIDE // 2
    quads = jax.ShapeDtypeStruct((4, h, h), jnp.bfloat16, sharding=one)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one)
    cfg = sampler.ChainConfig(beta=0.5, n_sweeps=200, block_size=BS)
    compiled = jax.jit(lambda q, k: sampler.run_chain(q, k, cfg)).lower(
        quads, key).compile()
    assert _device_bytes(compiled) < HBM_BYTES


def test_sharded_pallas_lines_sweep_compiles_on_2x2(topo, mosaic):
    """The mesh path at 20480^2 per chip: Mosaic kernel inside shard_map,
    halo collectives across the 2x2 grid, per-device memory in budget."""
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("data", "model"))
    cfg = dising.DistIsingConfig(beta=0.5, backend="pallas_lines")
    m = 2 * TABLE1_SIDE // 2 // BS
    qb = jax.ShapeDtypeStruct((4, m, m, BS, BS), jnp.bfloat16,
                              sharding=dising.lattice_sharding(mesh, cfg))
    key = jax.ShapeDtypeStruct((2,), jnp.uint32,
                               sharding=NamedSharding(
                                   mesh, jax.sharding.PartitionSpec()))
    run = dising.make_run_sweeps_fn(mesh, cfg, 10)
    compiled = run.lower(qb, key).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "collective-permute" in text
    assert _device_bytes(compiled) < HBM_BYTES
