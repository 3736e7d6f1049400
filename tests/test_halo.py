"""HaloSpec unit suite: the N-D halo plane's edge providers round-trip
under shard_map on 1-, 2-, and 3-axis shard grids (ISSUE 5 tentpole).

The invariant everywhere: ``spec.neighbor(local, dim, delta)`` on each
device-local patch, gathered back to the global view, must equal
``jnp.roll(global, -delta, dim)`` — i.e. the halo'd roll IS the global
torus roll, for any decomposition. Mesh tests run in subprocesses (the
main pytest process stays single-device; see conftest)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jax.sharding import PartitionSpec as P

from jax.sharding import AbstractMesh
from repro.distributed import halo


def test_from_mesh_static_properties():
    mesh = AbstractMesh((2, 4, 2), ("pod", "data", "model"))
    spec = halo.HaloSpec.from_mesh(mesh, (("pod", "data"), "model", None))
    assert spec.ndim == 3
    assert spec.shard_counts() == (8, 2, 1)
    assert spec.n_devices() == 16
    assert spec.mesh_axis_names() == ("pod", "data", "model")
    assert spec.axes[0].mesh_axes == ("pod", "data")
    assert spec.axes[2].mesh_axes == ()


def test_partition_spec_layouts():
    mesh = AbstractMesh((2, 2), ("data", "model"))
    spec = halo.HaloSpec.from_mesh(mesh, ("data", "model"))
    assert spec.partition_spec() == P(("data",), ("model",))
    assert spec.partition_spec(leading=1, trailing=2) == \
        P(None, ("data",), ("model",), None, None)
    spec3 = halo.HaloSpec.from_mesh(mesh, (None, "data", "model"))
    assert spec3.partition_spec() == P(None, ("data",), ("model",))


def test_spec2d_matches_legacy_vocabulary():
    spec = halo.spec2d(("pod", "data"), "model", 4, 2)
    assert spec.shard_counts() == (4, 2)
    assert spec.axes[0].mesh_axes == ("pod", "data")
    assert spec.axes[1].mesh_axes == ("model",)


def test_neighbor_and_index_unsharded_is_local_roll():
    """On a 1x1 mesh every primitive must degrade to plain torus ops."""
    from jax import shard_map
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1, 1), ("data", "model"))
    spec = halo.HaloSpec.from_mesh(mesh, ("data", "model"))
    x = jnp.arange(48, dtype=jnp.float32).reshape(6, 8)

    def body(x):
        return (spec.neighbor(x, 0, +1), spec.neighbor(x, 1, -1),
                spec.global_index(x.shape))

    got_s, got_w, gi = shard_map(
        body, mesh=mesh, check_vma=False,
        in_specs=(spec.partition_spec(),),
        out_specs=(spec.partition_spec(),) * 3)(x)
    np.testing.assert_array_equal(np.asarray(got_s),
                                  np.roll(np.asarray(x), -1, 0))
    np.testing.assert_array_equal(np.asarray(got_w),
                                  np.roll(np.asarray(x), 1, 1))
    np.testing.assert_array_equal(np.asarray(gi),
                                  np.arange(48).reshape(6, 8))


_GRID_CASES = [
    # (mesh shape, mesh axes, lattice axes mapping, array rank, devices)
    ("(4,)", "('data',)", "('data', None)", 2, 4),
    ("(2, 2)", "('data', 'model')", "('data', 'model')", 2, 4),
    ("(2, 2, 2)", "('pod', 'data', 'model')",
     "('pod', 'data', 'model')", 3, 8),
    ("(2, 4)", "('data', 'model')", "(None, ('data', 'model'), None)",
     3, 8),
]


@pytest.mark.parametrize("mesh_shape,axes,lat_axes,rank,devices",
                         _GRID_CASES)
def test_neighbor_round_trips_under_shard_map(subproc, mesh_shape, axes,
                                              lat_axes, rank, devices):
    """Gathered spec.neighbor == global jnp.roll for every dim and both
    directions, on 1-, 2-, and 3-axis shard grids (2-D and 3-D arrays)."""
    out = subproc(f"""
    import jax, jax.numpy as jnp, numpy as np
    from jax import shard_map
    from repro.distributed import halo
    from repro.launch import mesh as mesh_lib

    mesh = mesh_lib.make_mesh({mesh_shape}, {axes})
    spec = halo.HaloSpec.from_mesh(mesh, {lat_axes})
    shape = (8, 8) if {rank} == 2 else (4, 8, 8)
    x = jnp.arange(np.prod(shape), dtype=jnp.float32).reshape(shape)
    xs = jax.device_put(x, jax.sharding.NamedSharding(
        mesh, spec.partition_spec()))

    for dim in range(spec.ndim):
        for delta in (+1, -1):
            f = shard_map(lambda a: spec.neighbor(a, dim, delta),
                          mesh=mesh, check_vma=False,
                          in_specs=(spec.partition_spec(),),
                          out_specs=spec.partition_spec())
            got = jax.device_get(jax.jit(f)(xs))
            want = np.roll(np.asarray(x), -delta, dim)
            assert (got == want).all(), (dim, delta)

    gi = shard_map(lambda a: spec.global_index(a.shape), mesh=mesh,
                   check_vma=False, in_specs=(spec.partition_spec(),),
                   out_specs=spec.partition_spec())(xs)
    assert (jax.device_get(gi).reshape(-1)
            == np.arange(np.prod(shape))).all()
    print("HALO_ND_OK")
    """, devices=devices)
    assert "HALO_ND_OK" in out


def test_blocked_quad_edges_match_gathered_default(subproc):
    """The 2-D blocked-quad provider (the Algorithm-2 halo contract) must
    produce, per device, exactly the slice of the single-device
    ``default_edges`` of the gathered lattice — for all four sides."""
    out = subproc("""
    import jax, jax.numpy as jnp, numpy as np
    from jax import shard_map
    from repro.core import checkerboard as cb, lattice as L
    from repro.distributed import halo
    from repro.launch import mesh as mesh_lib

    mesh = mesh_lib.make_mesh((2, 2), ("data", "model"))
    spec = halo.spec2d(("data",), ("model",), 2, 2)
    edges = halo.blocked_quad_edges(spec)
    mr = mc = 4; bs = 8
    xb = L.block(jnp.arange((mr * bs) * (mc * bs),
                            dtype=jnp.float32).reshape(mr * bs, mc * bs),
                 bs)
    qspec = spec.partition_spec(trailing=2)
    xs = jax.device_put(xb, jax.sharding.NamedSharding(mesh, qspec))

    for side in ("north", "south", "west", "east"):
        f = shard_map(lambda a: edges(a, side), mesh=mesh,
                      check_vma=False, in_specs=(qspec,),
                      out_specs=spec.partition_spec(trailing=1))
        got = jax.device_get(jax.jit(f)(xs))
        want = np.asarray(cb.default_edges(xb, side))
        assert (got == want).all(), side
    print("QUAD_EDGES_OK")
    """, devices=4)
    assert "QUAD_EDGES_OK" in out