"""N-dimensional halo exchange for spatially-decomposed lattices (§4.2.2).

The paper splits the lattice into per-core sub-lattices and exchanges
boundary values with ``collective_permute`` over the TPU torus, and notes
the scheme "can be easily generalized" to any dimension. The JAX analogue
is ``jax.lax.ppermute`` inside ``jax.shard_map``; this module owns the ONE
ppermute vocabulary every decomposed plane in the repo speaks:

* :class:`HaloSpec` — a static description of how the d lattice axes map
  onto mesh axes (one :class:`HaloAxis` per lattice dimension: mesh axis
  names + shard count). From it every plane derives the three primitives:

  - ``send(plane, dim, delta)``   — shift a boundary plane ``delta`` hops
    along the device ring of lattice axis ``dim`` (identity when that axis
    is unsharded, so single-device code paths need no branches);
  - ``neighbor(x, dim, delta)``   — the halo'd roll: each site's neighbour
    value at ``+delta`` along ``dim``, with the torus-wrap plane replaced
    by the line received from the adjacent device;
  - ``offsets`` / ``global_index`` — traced global coordinates of the
    device-local patch, feeding the counter-based RNG schemes that make
    sharded chains bitwise-identical to single-device chains.

* :func:`halo_edges` — the 2-D blocked-quad edge provider with the same
  ``edges(xb, side)`` contract as ``repro.core.checkerboard.default_edges``
  (interior blocks resolve locally via rolls, device-boundary blocks are
  overwritten with the neighbouring device's line), now built on a 2-axis
  :class:`HaloSpec` instead of hard-coded (row, col) ppermute pairs. Each
  device sends one spin line per quad per colour update — 2*bs*mc bytes
  against ~mr*mc*bs^2 matmul work, which is why the paper observes linear
  scaling.

Consumers: the 2-D Ising quad path (:mod:`repro.distributed.ising`), the
3-D cube path (:mod:`repro.distributed.ising3d`), the sharded cluster
label merge (:mod:`repro.cluster.mesh`), and the Potts checkerboard /
cluster meshes (:mod:`repro.potts.mesh`).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import checkerboard as cb
from repro.core import lattice as L


def _perm(n: int, delta: int):
    """src -> dst pairs shifting data by ``delta`` along a ring of size n."""
    return [(k, (k + delta) % n) for k in range(n)]


def _as_tuple(axes) -> tuple:
    if axes is None:
        return ()
    if isinstance(axes, str):
        return (axes,)
    return tuple(axes)


def axis_size(mesh, axes) -> int:
    size = 1
    for a in _as_tuple(axes):
        size *= mesh.shape[a]
    return size


def _slc(ndim: int, dim: int, i):
    """Index tuple selecting plane ``i`` of axis ``dim`` (others full)."""
    idx = [slice(None)] * ndim
    idx[dim] = i
    return tuple(idx)


@dataclasses.dataclass(frozen=True)
class HaloAxis:
    """One lattice axis of a decomposition: which mesh axes shard it (an
    empty tuple = unsharded/replicated) and the static shard count."""
    mesh_axes: tuple = ()
    n_shards: int = 1


@dataclasses.dataclass(frozen=True)
class HaloSpec:
    """Static d-axis decomposition: ``axes[i]`` shards lattice axis i."""
    axes: tuple  # of HaloAxis, one per lattice dimension

    @classmethod
    def from_mesh(cls, mesh, lattice_axes) -> "HaloSpec":
        """Build from per-lattice-dim mesh axis names (str, tuple, or None
        for an unsharded dim); shard counts come from ``mesh.shape``."""
        return cls(tuple(
            HaloAxis(_as_tuple(a), axis_size(mesh, a))
            for a in lattice_axes))

    @property
    def ndim(self) -> int:
        return len(self.axes)

    def shard_counts(self) -> tuple:
        return tuple(ax.n_shards for ax in self.axes)

    def n_devices(self) -> int:
        n = 1
        for ax in self.axes:
            n *= ax.n_shards
        return n

    def mesh_axis_names(self) -> tuple:
        """All mesh axis names this decomposition shards over, flattened in
        lattice-dim order — the axes psum'd stats reduce over."""
        names: tuple = ()
        for ax in self.axes:
            names += ax.mesh_axes
        return names

    def partition_spec(self, leading: int = 0, trailing: int = 0):
        """PartitionSpec placing each lattice dim on its mesh axes, with
        ``leading``/``trailing`` extra unsharded dims (e.g. the quad axis
        of the blocked layout, or the [bs, bs] tile dims)."""
        from jax.sharding import PartitionSpec as P
        entries = [None] * leading
        for ax in self.axes:
            entries.append(ax.mesh_axes or None)
        entries += [None] * trailing
        return P(*entries)

    # -- traced per-device geometry (shard_map body only) -----------------

    def axis_index(self, dim: int) -> jax.Array:
        """This device's position along lattice axis ``dim``'s shard grid
        (0 when unsharded)."""
        ax = self.axes[dim]
        if not ax.mesh_axes:
            return jnp.int32(0)
        return lax.axis_index(ax.mesh_axes).astype(jnp.int32)

    def linear_device_index(self) -> jax.Array:
        """Row-major linear index over the full shard grid."""
        idx = jnp.int32(0)
        for dim in range(self.ndim):
            idx = idx * self.axes[dim].n_shards + self.axis_index(dim)
        return idx

    def offsets(self, local_shape: tuple) -> tuple:
        """Traced global coordinate of the local patch origin, per dim."""
        return tuple(self.axis_index(d) * local_shape[d]
                     for d in range(self.ndim))

    def global_shape(self, local_shape: tuple) -> tuple:
        return tuple(local_shape[d] * self.axes[d].n_shards
                     for d in range(self.ndim))

    def global_index(self, local_shape: tuple) -> jax.Array:
        """int32 [*local_shape] global linear site indices of the local
        patch — the counters the decomposition-independent RNG hashes."""
        offs = self.offsets(local_shape)
        gshape = self.global_shape(local_shape)
        gi = jnp.zeros((1,) * self.ndim, jnp.int32)
        for d in range(self.ndim):
            coord = offs[d] + jnp.arange(local_shape[d], dtype=jnp.int32)
            shape = [1] * self.ndim
            shape[d] = local_shape[d]
            gi = gi * jnp.int32(gshape[d]) + coord.reshape(shape)
        return jnp.broadcast_to(gi, local_shape)

    # -- the ppermute primitives ------------------------------------------

    def send(self, plane: jax.Array, dim: int, delta: int) -> jax.Array:
        """Shift ``plane`` by ``delta`` hops along axis ``dim``'s device
        ring (device k receives the plane of device k - delta); identity
        when the axis is unsharded, matching the local torus wrap."""
        ax = self.axes[dim]
        if ax.n_shards == 1:
            return plane
        with jax.named_scope(L.HALO):
            return lax.ppermute(plane, ax.mesh_axes,
                                _perm(ax.n_shards, delta))

    def plane(self, x: jax.Array, dim: int, delta: int) -> jax.Array:
        """The boundary plane this device's ``delta``-neighbour along
        ``dim`` contributes to the halo: its first plane for delta=+1,
        its last for delta=-1 (local wrap when unsharded)."""
        src = 0 if delta > 0 else -1
        return self.send(x[_slc(x.ndim, dim, src)], dim, -delta)

    def neighbor(self, x: jax.Array, dim: int, delta: int) -> jax.Array:
        """Each site's neighbour value ``delta`` steps along ``dim`` on the
        global torus: a local roll with the wrap plane overwritten by the
        adjacent device's boundary plane (one ppermute per sharded edge)."""
        ax = self.axes[dim]
        with jax.named_scope(L.HALO):
            out = jnp.roll(x, -delta, dim)
            if ax.n_shards > 1:
                dst = -1 if delta > 0 else 0
                out = out.at[_slc(x.ndim, dim, dst)].set(
                    self.plane(x, dim, delta))
            return out


# ---------------------------------------------------------------------------
# 2-D blocked-quad edge provider (the Algorithm-2 halo contract)
# ---------------------------------------------------------------------------


def spec2d(row_axes, col_axes, nrows: int, ncols: int) -> HaloSpec:
    """2-axis HaloSpec from the legacy (row_axes, col_axes) vocabulary."""
    return HaloSpec((HaloAxis(_as_tuple(row_axes), nrows),
                     HaloAxis(_as_tuple(col_axes), ncols)))


def blocked_quad_edges(spec: HaloSpec):
    """Edge provider for device-local blocked quads [mr, mc, bs, bs].

    Same contract as ``repro.core.checkerboard.default_edges``: interior
    blocks resolve locally via rolls; blocks on a sharded device boundary
    are overwritten with the line ppermuted from the neighbouring device.
    """
    rows, cols = spec.axes[0], spec.axes[1]

    def edges(xb: jax.Array, side: str) -> jax.Array:
        e = cb.default_edges(xb, side)          # local torus roll
        if side == "north" and rows.n_shards > 1:
            e = e.at[0].set(spec.send(xb[-1, :, -1, :], 0, +1))
        elif side == "south" and rows.n_shards > 1:
            e = e.at[-1].set(spec.send(xb[0, :, 0, :], 0, -1))
        elif side == "west" and cols.n_shards > 1:
            e = e.at[:, 0].set(spec.send(xb[:, -1, :, -1], 1, +1))
        elif side == "east" and cols.n_shards > 1:
            e = e.at[:, -1].set(spec.send(xb[:, 0, :, 0], 1, -1))
        return e

    return edges


def halo_edges(row_axes, col_axes, nrows: int, ncols: int):
    """Legacy 2-D entry point (kept for the quad planes): an
    ``edges(xb, side)`` provider over device-local [mr, mc, bs, bs] quads,
    now a thin binding of :func:`blocked_quad_edges` over a 2-axis
    :class:`HaloSpec`."""
    return blocked_quad_edges(spec2d(row_axes, col_axes, nrows, ncols))
