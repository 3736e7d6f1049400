"""Multi-device Ising sampler: spatial domain decomposition over the mesh.

The global lattice (compact blocked layout ``[4, MR, MC, bs, bs]``) is
sharded with grid rows over ``row_axes`` (``("pod", "data")`` on the
multi-pod mesh — the pod axis extends the lattice, exactly like adding more
TPU units extends the simulated system in the paper's Table 2) and grid cols
over ``col_axes`` (``"model"``). Inside ``jax.shard_map`` each device updates
its sub-lattice with the same compact Algorithm-2 math as the single-device
path, with halos crossing the interconnect via ``lax.ppermute``.

RNG: each device folds the chain key with its linear device index, then with
(step, colour) — fully counter-based, no cross-device RNG traffic, and
independent of how many devices participate.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from jax import shard_map
from repro.core import checkerboard as cb
from repro.core import lattice as L
from repro.core import measure
from repro.core import update_rules
from repro.distributed import decomp
from repro.distributed import halo
from repro.kernels import ops as kops


@dataclasses.dataclass(frozen=True)
class DistIsingConfig:
    beta: float
    block_size: int = L.MXU_BLOCK
    row_axes: tuple = ("data",)
    col_axes: tuple = ("model",)
    accept: str = "lut"
    backend: str = "xla"        # "xla" | "pallas_lines"
    prob_dtype: str = "float32"
    # §Perf pipeline: "paper" = f32 uniforms + float acceptance (faithful);
    # "opt" = rbg bit generation + bf16 nn + exact integer-threshold
    # acceptance (beyond-paper; bitwise-identical flip decisions to the
    # f32-LUT path — see core.checkerboard.acceptance_thresholds_u24).
    pipeline: str = "paper"
    bits_dtype: str = "uint32"  # "uint16" halves RNG traffic (opt only)
    rng: str = "threefry"       # "threefry" | "rbg" (lax.rng_bit_generator)
    rule: str = "metropolis"    # update_rules name: "metropolis"|"heat_bath"

    def probs_rule(self) -> str:
        """Registry name for the float-probs (paper-pipeline) path."""
        return ("heat_bath" if self.rule == "heat_bath" else self.accept)

    def bits_rule(self) -> str:
        """Registry name for the bits paths (opt pipeline / Pallas)."""
        return ("heat_bath" if self.rule == "heat_bath"
                else "metropolis_lut")


def lattice_spec(cfg: DistIsingConfig) -> P:
    """PartitionSpec for the [4, MR, MC, bs, bs] global blocked quads."""
    return P(None, cfg.row_axes, cfg.col_axes, None, None)


def lattice_sharding(mesh, cfg: DistIsingConfig) -> NamedSharding:
    return NamedSharding(mesh, lattice_spec(cfg))


def _device_key(key: jax.Array, cfg: DistIsingConfig, ncols: int) -> jax.Array:
    with jax.named_scope(L.RNG):
        row = jax.lax.axis_index(cfg.row_axes)
        col = jax.lax.axis_index(cfg.col_axes)
        return jax.random.fold_in(key, row * ncols + col)


def _draw_bits(k: jax.Array, shape, cfg: DistIsingConfig) -> jax.Array:
    """Counter-based random bits for one colour update.

    "rbg" uses the XLA RngBitGenerator op — one fused HLO instead of the
    multi-kilofusion threefry pipeline (the §Perf Ising iteration 1 win:
    threefry bit generation was 57% of all HBM traffic in the baseline).
    """
    dt = jnp.dtype(cfg.bits_dtype)
    if cfg.rng == "rbg":
        kd = jax.random.key_data(k).astype(jnp.uint32).reshape(-1)
        rbg_key = jnp.concatenate([kd, kd])[:4] if kd.size < 4 else kd[:4]
        # algorithm 0 = RNG_DEFAULT: the platform generator (hardware RBG
        # on TPU; one HLO op instead of the threefry fusion pipeline).
        _, bits = jax.lax.rng_bit_generator(rbg_key, shape, dtype=dt,
                                            algorithm=0)
        return bits
    return jax.random.bits(k, shape, dt)


def _flip_int(sigma, nn, bits, beta):
    """Integer-threshold Metropolis flip (exact; see
    ``update_rules.metropolis_thresholds_u24``).

    nn*sigma is exact in bf16 (values in {-4..4}); thresholds are compared
    against the top 24 bits (uint32) or all 16 bits (uint16, thresholds
    rescaled to 2^16 with ceil — a 2^-16-granular acceptance, statistically
    indistinguishable and half the RNG traffic)."""
    return update_rules.metropolis_int.flip_bits_int(sigma, nn, bits, beta)


def _local_color_update(quads, key, step, color, cfg, edges,
                        return_stats: bool = False):
    """One colour update; quads is a 4-TUPLE (a, b, c, d) of device-local
    [mr, mc, bs, bs] arrays. Tuple-carry (not a stacked [4, ...] tensor)
    avoids a full-lattice restack round-trip per colour (§Perf Ising it. 3).

    ``return_stats`` additionally returns ``(new0, new1, nn0, nn1)`` so the
    streaming measurement plane can form the bond energy from the sums the
    update already computed (XLA backend only — the Pallas kernel keeps nn
    in VMEM; callers fall back to ``measure.blocked_stats`` there).
    """
    with jax.named_scope(L.RNG):
        k = jax.random.fold_in(jax.random.fold_in(key, step), color)
    a, b, c, d = quads
    blk = a.shape
    if cfg.backend == "pallas_lines":
        with jax.named_scope(L.RNG):
            bits = jax.random.bits(k, (2,) + blk, jnp.uint32)
        out = kops.update_color(jnp.stack(quads), bits, cfg.beta, color,
                                backend="pallas_lines", edges=edges,
                                rule=cfg.bits_rule())
        out = tuple(out[i] for i in range(4))
        return (out, None) if return_stats else out
    kh = L.kernel_compact(a.shape[-1], a.dtype)
    if color == 0:
        nn0, nn1 = cb.nn_black(a, b, c, d, kh, edges)
        s0, s1 = a, d
    else:
        nn0, nn1 = cb.nn_white(a, b, c, d, kh, edges)
        s0, s1 = b, c
    if cfg.pipeline == "opt":
        rule = update_rules.get_rule(cfg.bits_rule())
        with jax.named_scope(L.RNG):
            bits = _draw_bits(k, (2,) + blk, cfg)
        with jax.named_scope(L.FLIP):
            new0 = rule.flip_bits_int(s0, nn0.astype(s0.dtype), bits[0],
                                      cfg.beta)
            new1 = rule.flip_bits_int(s1, nn1.astype(s1.dtype), bits[1],
                                      cfg.beta)
    else:  # paper-faithful float pipeline
        with jax.named_scope(L.RNG):
            probs = jax.random.uniform(k, (2,) + blk,
                                       jnp.dtype(cfg.prob_dtype))
        new0 = cb._flip(s0, nn0.astype(s0.dtype), probs[0], cfg.beta,
                        cfg.probs_rule())
        new1 = cb._flip(s1, nn1.astype(s1.dtype), probs[1], cfg.beta,
                        cfg.probs_rule())
    out = (new0, b, c, new1) if color == 0 else (a, new0, new1, d)
    if return_stats:
        return out, (new0, new1, nn0, nn1)
    return out


def make_sweep_fn(mesh, cfg: DistIsingConfig):
    """Returns jitted ``sweep(qb_global, key, step) -> qb_global``."""
    nrows = halo.axis_size(mesh, cfg.row_axes)
    ncols = halo.axis_size(mesh, cfg.col_axes)
    spec = lattice_spec(cfg)

    def local_sweep(qb, key, step):
        edges = halo.halo_edges(cfg.row_axes, cfg.col_axes, nrows, ncols)
        dkey = _device_key(key, cfg, ncols)
        quads = tuple(qb[i] for i in range(4))
        for color in (0, 1):
            quads = _local_color_update(quads, dkey, step, color, cfg, edges)
        return jnp.stack(quads)

    mapped = shard_map(local_sweep, mesh=mesh, check_vma=False,
                           in_specs=(spec, P(), P()), out_specs=spec)
    return jax.jit(mapped, donate_argnums=(0,))


def make_sweep_tuple_fn(mesh, cfg: DistIsingConfig):
    """Sweep over a 4-TUPLE of [MR, MC, bs, bs] quad arrays (no stacked
    [4, ...] axis): avoids the full-lattice restack a stacked carry pays
    every sweep. This is the layout the dry-run cell lowers (§Perf Ising
    iteration 4); the production chunked runner amortizes the stack."""
    nrows = halo.axis_size(mesh, cfg.row_axes)
    ncols = halo.axis_size(mesh, cfg.col_axes)
    qspec = P(cfg.row_axes, cfg.col_axes, None, None)

    def local_sweep(a, b, c, d, key, step):
        edges = halo.halo_edges(cfg.row_axes, cfg.col_axes, nrows, ncols)
        dkey = _device_key(key, cfg, ncols)
        quads = (a, b, c, d)
        for color in (0, 1):
            quads = _local_color_update(quads, dkey, step, color, cfg, edges)
        return quads

    mapped = shard_map(local_sweep, mesh=mesh, check_vma=False,
                           in_specs=(qspec,) * 4 + (P(), P()),
                           out_specs=(qspec,) * 4)
    return jax.jit(mapped, donate_argnums=(0, 1, 2, 3))


def halo_spec(mesh, cfg: DistIsingConfig) -> halo.HaloSpec:
    """The 2-axis :class:`repro.distributed.halo.HaloSpec` of this config."""
    return halo.spec2d(cfg.row_axes, cfg.col_axes,
                       halo.axis_size(mesh, cfg.row_axes),
                       halo.axis_size(mesh, cfg.col_axes))


def mesh_model(mesh, cfg: DistIsingConfig) -> decomp.MeshModel:
    """The 2-D Ising quad binding of the generic decomposition driver:
    the per-colour Algorithm-2 update as the site rule, blocked-quad halo
    edges from the :class:`HaloSpec`, and the fused measured sweep that
    reuses the white half-update's own nn sums (XLA backend)."""
    spec = halo_spec(mesh, cfg)
    ncols = spec.axes[1].n_shards
    edges = halo.blocked_quad_edges(spec)
    axes = _stats_axes(cfg)
    n_dev = spec.n_devices()

    def sweep(quads, key, step):
        with jax.named_scope(L.SWEEP):
            dkey = _device_key(key, cfg, ncols)
            for color in (0, 1):
                quads = _local_color_update(quads, dkey, step, color, cfg,
                                            edges)
            return quads

    def stats(quads):
        n_spins = 4 * quads[0].size * n_dev
        return measure.blocked_stats(quads, n_spins, edges=edges,
                                     axis_names=axes)

    def sweep_measured(quads, key, step):
        with jax.named_scope(L.SWEEP):
            dkey = _device_key(key, cfg, ncols)
            n_spins = 4 * quads[0].size * n_dev
            quads = _local_color_update(quads, dkey, step, 0, cfg, edges)
            quads, st = _local_color_update(quads, dkey, step, 1, cfg,
                                            edges, return_stats=True)
            if st is not None:
                new0, new1, nn0, nn1 = st
                m = measure.magnetization_mean(quads, n_spins, axes)
                e = measure.bond_energy_from_nn(new0, new1, nn0, nn1,
                                                n_spins, axes)
            else:  # pallas_lines: nn stays in VMEM; one stencil recompute
                m, e = measure.blocked_stats(quads, n_spins, edges=edges,
                                             axis_names=axes)
            return quads, (m, e)

    return decomp.MeshModel(
        state_spec=lattice_spec(cfg), sweep=sweep, stats=stats,
        sweep_measured=sweep_measured,
        unpack=lambda qb: tuple(qb[i] for i in range(4)),
        pack=jnp.stack)


def make_run_sweeps_fn(mesh, cfg: DistIsingConfig, n_sweeps: int):
    """Returns jitted ``run(qb_global, key) -> qb_global`` (n_sweeps sweeps,
    measurement-free — the paper's throughput benchmark loop)."""
    return decomp.make_run_sweeps_fn(mesh, mesh_model(mesh, cfg), n_sweeps)


def make_sweep_with_bits_fn(mesh, cfg: DistIsingConfig):
    """Test entry point: sweep consuming explicit global bit tensors
    [2, 2, MR, MC, bs, bs] (colour-major), sharded like the lattice — lets
    tests compare multi-device vs single-device output bitwise."""
    nrows = halo.axis_size(mesh, cfg.row_axes)
    ncols = halo.axis_size(mesh, cfg.col_axes)
    spec = lattice_spec(cfg)
    bits_spec = P(None, None, cfg.row_axes, cfg.col_axes, None, None)

    def local_sweep(qb, bits):
        edges = halo.halo_edges(cfg.row_axes, cfg.col_axes, nrows, ncols)
        for color in (0, 1):
            qb = kops.update_color(qb, bits[color], cfg.beta, color,
                                   backend="pallas_lines", edges=edges)
        return qb

    mapped = shard_map(local_sweep, mesh=mesh, check_vma=False,
                           in_specs=(spec, bits_spec), out_specs=spec)
    return jax.jit(mapped)


def _stats_axes(cfg: DistIsingConfig) -> tuple:
    """Mesh axes the streamed scalars psum over (rows + cols, flattened)."""
    row = (cfg.row_axes,) if isinstance(cfg.row_axes, str) else cfg.row_axes
    col = (cfg.col_axes,) if isinstance(cfg.col_axes, str) else cfg.col_axes
    return tuple(row) + tuple(col)


def make_run_chain_fn(mesh, cfg: DistIsingConfig, n_sweeps: int,
                      measure_every: int = 1):
    """Measured mesh chain: ``run(qb_global, key) -> (qb_global, Moments)``.

    The streaming measurement plane inside the shard_map loop: per-sweep
    (m, E) come from the white half-update's own nn sums (XLA backend) or
    one blocked-stencil recompute (Pallas backend), psum-reduced to exact
    global scalars, and accumulated into running ``(|m|, E, m2, m4)``
    moments with ``measure_every`` thinning — no ``from_quads``, no host
    round-trips, and the same fori_loop structure as the throughput path.

    Replaces the old magnetization-only ``magnetization_global`` helper:
    mesh runs now stream the full Fig.-4 moment set.
    """
    return decomp.make_run_chain_fn(mesh, mesh_model(mesh, cfg), n_sweeps,
                                    measure_every)


def global_stats(mesh, cfg: DistIsingConfig):
    """Jitted exact (m, E/spin) of the sharded blocked lattice — the
    standalone companion of :func:`make_run_chain_fn` for logging between
    compiled chunks (supersedes ``magnetization_global``)."""
    return decomp.global_stats(mesh, mesh_model(mesh, cfg))
