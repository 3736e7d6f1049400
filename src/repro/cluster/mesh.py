"""Sharded cluster updates: Swendsen-Wang / Wolff under ``shard_map``.

The lattice stays in the production blocked layout ``[4, MR, MC, bs, bs]``
sharded over the mesh (``distributed.ising.lattice_spec``). Each sweep a
device reconstructs its *local full view* (a contiguous [lh, lw] spatial
patch of the global lattice — blocked grid rows/cols shard contiguously),
then:

1. **Bonds.** Spin halo lines arrive via one ``ppermute`` per direction;
   bond uniforms are counter hashes of *global* bond indices
   (:mod:`repro.cluster.bonds`), so every device draws exactly the bonds
   the single-device path draws — boundary bonds are computed identically
   on both sides with zero bond-RNG traffic.
2. **Local labeling.** Connected components of the device-interior bond
   graph in local-index space (:func:`repro.cluster.label.label_components`
   — fast pointer-jumped convergence), then each local root is rewritten
   as its global linear index.
3. **Global merge.** A ``while_loop``: exchange boundary label lines via
   ``ppermute``, min-merge across active cross-device bonds, collapse each
   local cluster to its new minimum with one ``segment_min`` over the
   (fixed) local roots, and stop when a global ``psum``-reduced changed
   flag clears. Labels converge to the per-cluster minimum global index —
   the same canonical labels the single-device path produces, exactly.
4. **Flip.** The per-cluster coin is the same gather-free label hash as on
   one device; a Wolff seed site is drawn from the replicated sweep key
   and its label recovered with one masked-sum ``psum``.

Because every random decision is a counter hash of global indices, the
sharded chain is **bitwise identical** to the single-device chain
(``tests/test_cluster.py`` pins labels and states on a 2x2 device grid).

Measurement reuses the streaming plane: post-flip (m, E) via
``measure.blocked_stats`` with halo edges, psum-reduced, accumulated into
running :class:`repro.core.measure.Moments`.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from jax import shard_map
from repro.cluster import bonds as B
from repro.cluster import label as LBL
from repro.core import lattice as L
from repro.core import measure
from repro.distributed import decomp
from repro.distributed import halo
from repro.distributed import ising as dising

_INT_MAX = jnp.iinfo(jnp.int32).max


def _local_full(qb) -> jax.Array:
    """[4, mr, mc, bs, bs] device-local blocked quads -> [lh, lw] full view."""
    quads = jnp.stack([L.unblock(qb[i]) for i in range(4)])
    return L.from_quads(quads)


def _local_blocked(full: jax.Array, bs: int) -> jax.Array:
    q = L.to_quads(full)
    return jnp.stack([L.block(q[i], bs) for i in range(4)])


def _spec(cfg, nrows: int, ncols: int) -> halo.HaloSpec:
    return halo.spec2d(cfg.row_axes, cfg.col_axes, nrows, ncols)


def _device_geometry(qb_local, cfg, nrows: int, ncols: int):
    """(lh, lw, roff, coff, H, W, gi): local patch extents (static) and
    traced global offsets / index grid."""
    _, mrl, mcl, bs, _ = qb_local.shape
    lh, lw = 2 * mrl * bs, 2 * mcl * bs
    spec = _spec(cfg, nrows, ncols)
    roff, coff = spec.offsets((lh, lw))
    H, W = lh * nrows, lw * ncols
    gi = B.global_index(lh, lw, roff, coff, W)
    return lh, lw, roff, coff, H, W, gi


def global_labels_local(lf, key, cfg, threshold, geometry, nrows, ncols):
    """Stages 1-3 of a sharded cluster sweep: FK bonds with spin halos,
    device-local labeling, and the ppermute/segment_min global merge.

    Returns the device-local ``[lh, lw]`` patch of the *global* canonical
    (per-cluster minimum global index) labels — bitwise what the
    single-device ``label_components`` produces on the full lattice.

    Spin-model agnostic: bonds activate on *equality* of ``lf`` entries,
    so +-1 Ising spins and integer Potts colours (:mod:`repro.potts.mesh`)
    share this machinery; only ``threshold`` and the per-cluster decision
    applied afterwards differ.
    """
    lh, lw, roff, coff, H, W, gi = geometry
    spec = _spec(cfg, nrows, ncols)
    kb = jax.random.fold_in(key, 0)

    # -- 1. bonds (with spin halos at device boundaries) -------------------
    east = spec.neighbor(lf, 1, +1)
    south = spec.neighbor(lf, 0, +1)
    br, bd = B.fk_bonds(lf, kb, threshold, east=east, south=south, gi=gi)

    # Boundary bonds owned by the west/north neighbour, recomputed locally
    # from the same global counters (only needed across real device edges).
    if ncols > 1:
        west_spin = spec.plane(lf, 1, -1)
        gi_w = ((roff + jnp.arange(lh, dtype=jnp.int32)) * W
                + (coff - 1) % W)
        bl0 = ((lf[:, 0] == west_spin)
               & B.active(B.bond_bits(kb, gi_w, 0), threshold))
    if nrows > 1:
        north_spin = spec.plane(lf, 0, -1)
        gi_n = (((roff - 1) % H) * W
                + coff + jnp.arange(lw, dtype=jnp.int32))
        bu0 = ((lf[0, :] == north_spin)
               & B.active(B.bond_bits(kb, gi_n, 1), threshold))

    # -- 2. local labeling (device-interior bonds, local-index space) ------
    br_loc = br if ncols == 1 else br.at[:, -1].set(False)
    bd_loc = bd if nrows == 1 else bd.at[-1, :].set(False)
    root = LBL.label_components(br_loc, bd_loc)          # local linear idx
    glab = ((roff + root // lw) * W + coff + root % lw)  # -> global idx

    # -- 3. global merge: ppermute boundary labels until psum(changed)=0 ---
    if nrows > 1 or ncols > 1:
        root_flat = root.reshape(-1)
        axes = dising._stats_axes(cfg)

        def cond(carry):
            return carry[1]

        def body(carry):
            lab, _ = carry
            new = lab
            if ncols > 1:
                east_lab = spec.plane(lab, 1, +1)
                new = new.at[:, -1].min(
                    jnp.where(br[:, -1], east_lab, _INT_MAX))
                west_lab = spec.plane(lab, 1, -1)
                new = new.at[:, 0].min(jnp.where(bl0, west_lab, _INT_MAX))
            if nrows > 1:
                south_lab = spec.plane(lab, 0, +1)
                new = new.at[-1, :].min(
                    jnp.where(bd[-1, :], south_lab, _INT_MAX))
                north_lab = spec.plane(lab, 0, -1)
                new = new.at[0, :].min(jnp.where(bu0, north_lab, _INT_MAX))
            # hook: collapse every local cluster to its new minimum, so a
            # boundary improvement reaches the opposite boundary in ONE step
            seg = jax.ops.segment_min(new.reshape(-1), root_flat,
                                      num_segments=lh * lw)
            new = seg[root_flat].reshape(lh, lw)
            changed = lax.psum(
                jnp.any(new != lab).astype(jnp.int32), axes) > 0
            return new, changed

        glab, _ = lax.while_loop(cond, body, (glab, jnp.bool_(True)))
    return glab


def _local_cluster_sweep(lf, key, cfg, algorithm, threshold, geometry,
                         nrows, ncols):
    """One SW/Wolff update of the device-local full view ``lf``."""
    lh, lw, roff, coff, H, W, gi = geometry
    glab = global_labels_local(lf, key, cfg, threshold, geometry,
                               nrows, ncols)

    # -- 4. per-cluster flip (gather-free label hash) ----------------------
    if algorithm == "swendsen_wang":
        kf = jax.random.fold_in(key, 1)
        flip = (B.counter_bits(kf, glab) >> 31) == 1
    elif algorithm == "wolff":
        ks = jax.random.fold_in(key, 2)
        seed = jax.random.randint(ks, (), 0, H * W)
        local = jnp.sum(jnp.where(gi == seed, glab, 0))
        seed_label = lax.psum(local, dising._stats_axes(cfg))
        flip = glab == seed_label
    else:
        raise ValueError(f"unknown cluster algorithm {algorithm!r}")
    return jnp.where(flip, -lf, lf), glab


def mesh_model(mesh, cfg, algorithm: str) -> decomp.MeshModel:
    """The sharded-cluster binding of the generic decomposition driver:
    one SW/Wolff sweep of the device-local full view as the site rule,
    ``blocked_stats`` with HaloSpec edges as the measurement."""
    nrows = halo.axis_size(mesh, cfg.row_axes)
    ncols = halo.axis_size(mesh, cfg.col_axes)
    hspec = _spec(cfg, nrows, ncols)
    axes = dising._stats_axes(cfg)
    threshold = B.bond_threshold_u24(cfg.beta)
    edges = halo.blocked_quad_edges(hspec)
    n_dev = nrows * ncols

    def sweep(qb, key, step):
        bs = qb.shape[-1]
        geom = _device_geometry(qb, cfg, nrows, ncols)
        lf = _local_full(qb)
        k = jax.random.fold_in(key, step)
        new, _ = _local_cluster_sweep(lf, k, cfg, algorithm, threshold,
                                      geom, nrows, ncols)
        return _local_blocked(new, bs)

    def stats(qb):
        n_spins = 4 * qb[0].size * n_dev
        return measure.blocked_stats(qb, n_spins, edges=edges,
                                     axis_names=axes)

    return decomp.MeshModel(state_spec=dising.lattice_spec(cfg),
                            sweep=sweep, stats=stats)


def make_cluster_run_fn(mesh, cfg, algorithm: str, n_sweeps: int,
                        measure_every: int = 1):
    """Measured sharded cluster chain:
    ``run(qb_global, key) -> (qb_global, Moments)``."""
    return decomp.make_run_chain_fn(mesh, mesh_model(mesh, cfg, algorithm),
                                    n_sweeps, measure_every)


def make_cluster_sweeps_fn(mesh, cfg, algorithm: str, n_sweeps: int):
    """Measurement-free sharded cluster chain:
    ``run(qb_global, key) -> qb_global``."""
    return decomp.make_run_sweeps_fn(mesh, mesh_model(mesh, cfg, algorithm),
                                     n_sweeps)


def make_labels_fn(mesh, cfg):
    """Test entry point: ``labels(qb_global, key) -> [H, W] int32`` global
    canonical labels for one sweep's bond draw — compared bitwise against
    the single-device ``cluster.sweep.labels_for``."""
    nrows = halo.axis_size(mesh, cfg.row_axes)
    ncols = halo.axis_size(mesh, cfg.col_axes)
    spec = dising.lattice_spec(cfg)
    threshold = B.bond_threshold_u24(cfg.beta)

    def local_labels(qb, key):
        lf = _local_full(qb)
        geom = _device_geometry(qb, cfg, nrows, ncols)
        _, glab = _local_cluster_sweep(lf, key, cfg, "swendsen_wang",
                                      threshold, geom, nrows, ncols)
        return glab

    mapped = shard_map(local_labels, mesh=mesh, check_vma=False,
                       in_specs=(spec, P()),
                       out_specs=P(cfg.row_axes, cfg.col_axes))
    return jax.jit(mapped)
