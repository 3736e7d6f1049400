"""`IsingEngine`: one config-driven front door for every simulation scenario.

The paper's point is that a single small program drives the full distributed
checkerboard simulation; this module is that program's API. One
:class:`EngineConfig` selects across four orthogonal axes:

==============  =====================================================
axis            values
==============  =====================================================
backend         ``xla`` (Algorithm 2 in pure jnp, the paper-faithful
                path), ``pallas`` / ``pallas_lines`` / ``ref`` (the
                fused kernel stack in :mod:`repro.kernels`)
topology        ``single`` (one device) or ``mesh`` (spatial domain
                decomposition + halo exchange through the generic
                N-D halo plane: :mod:`repro.distributed.halo` /
                :mod:`repro.distributed.decomp`, with per-model bindings
                in ``distributed.ising``, ``distributed.ising3d``,
                ``cluster.mesh``, and ``potts.mesh``)
dims            2 (checkerboard quads) or 3 (:mod:`repro.core.ising3d`;
                ``topology="mesh"`` shards the [D, H, W] cube over a
                2- or 3-axis device grid, bitwise-equal to one device)
pipeline        ``paper`` (f32 uniforms + float acceptance) or ``opt``
                (integer-threshold acceptance, rbg-capable RNG — the
                beyond-paper fast path in ``distributed.ising``)
==============  =====================================================

plus the update-rule axis (``rule="metropolis" | "heat_bath"`` — one
:mod:`repro.core.update_rules` registry entry runs on every 2-D backend),
the algorithm axis (``algorithm="metropolis"`` for single-site
checkerboard dynamics, or ``"swendsen_wang"`` / ``"wolff"`` for the
cluster-update plane in :mod:`repro.cluster` — Fortuin-Kasteleyn bonds +
label-propagation components + hashed per-cluster flips, the fast-science
path at T_c where single-site dynamics critically slow down),
and the measurement plane: every measured run streams running
``(|m|, E, m^2, m^4)`` moments (:mod:`repro.core.measure`) out of the
compiled loop — including ``pipeline='opt'``, mesh topology, and the
Pallas backends, which used to be measurement-free-only —

plus the **model axis** (``model="ising" | "potts"``): the q-state Potts
model (:mod:`repro.potts`) runs through the same front door —
``EngineConfig(model="potts", q=3, algorithm="swendsen_wang")`` — with
integer-coded colour lattices, checkerboard heat-bath/Metropolis
(``rule=``), FK-bond Swendsen-Wang/Wolff (``algorithm=``), single or mesh
topology for BOTH dynamics families (the sharded cluster label merge and
the sharded int32-colour checkerboard are each bitwise equal to one
device), and vmapped multi-beta ensembles. For Potts runs, ``EngineResult.magnetization``
carries the scalar order parameter (q max_s rho_s - 1)/(q - 1) per sweep
and ``beta`` is the Potts coupling (q = 2 maps to Ising at
``beta_ising = beta_potts / 2``),

plus the ensemble axis, which is the genuinely new capability: setting
``betas`` (instead of scalar ``beta``) runs R independent replicas at
distinct temperatures in ONE jitted program — ``vmap`` over the replica
axis with per-sweep fused observable streaming (magnetization + energy
accumulated inside the compiled scan, never materializing lattices on the
host), so a phase-diagram scan is one engine call instead of a Python loop
over temperatures. On a mesh, replicas are sharded over the mesh axes
(``replica_axes``) — the natural use of a pod that is larger than one
lattice's decomposition needs. ``ensemble="tempering"`` swaps configurations
between adjacent replicas (parallel tempering, :mod:`repro.core.tempering`).

RNG contract (what makes the dispatch testable): replica ``i`` of an
ensemble run with chain key ``k`` evolves bitwise-identically to a
single-chain run with key ``fold_in(k, i)``; the single-device scalar-β XLA
path is bitwise-identical to calling :func:`repro.core.sampler.run_chain`
directly. Tests in ``tests/test_engine.py`` pin both.

The low-level modules stay importable for power users — the engine only
dispatches; it does not fork the math.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

from repro.core import checkerboard as cb
from repro.core import ising3d as I3
from repro.core import lattice as L
from repro.core import measure
from repro.core import observables as obs
from repro.core import sampler
from repro.core import tempering as pt
from repro.kernels import ops as kops

_BACKENDS = ("xla", "pallas", "pallas_lines", "ref")
_TOPOLOGIES = ("single", "mesh")
_PIPELINES = ("paper", "opt")
_ENSEMBLES = ("independent", "tempering")
_RULES = ("metropolis", "heat_bath")
_ALGORITHMS = ("metropolis", "swendsen_wang", "wolff")
_MODELS = ("ising", "potts")


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Everything the engine needs to pick a compiled program.

    Exactly one of ``beta`` (single chain) / ``betas`` (replica ensemble)
    must be set. ``size`` is the lattice side: an even [size, size] torus in
    2-D, a [size, size, size] cube in 3-D.
    """
    size: int
    width: int = 0                     # 2-D lattice width; 0 -> size (square)
    beta: Optional[float] = None       # None = unset (beta=0.0 is legal)
    betas: tuple = ()
    n_sweeps: int = 100

    model: str = "ising"               # ising | potts
    q: int = 0                         # Potts states (model="potts", >= 2)
    dims: int = 2                      # 2 | 3
    backend: str = "xla"               # xla | pallas | pallas_lines | ref
    topology: str = "single"           # single | mesh
    pipeline: str = "paper"            # paper | opt
    ensemble: str = "independent"      # independent | tempering

    mesh_shape: tuple = ()             # e.g. (2, 2); mesh topology only
    mesh_axes: tuple = ("data", "model")
    replica_axes: tuple = ("data",)    # ensemble sharding axes on a mesh

    exchange_every: int = 5            # tempering swap cadence (sweeps)
    accept: str = "lut"                # lut | exp (Metropolis table form)
    rule: str = "metropolis"           # metropolis | heat_bath (Glauber)
    algorithm: str = "metropolis"      # metropolis | swendsen_wang | wolff
    dtype: str = "bfloat16"
    prob_dtype: str = "float32"
    block_size: int = 0                # 0 -> min(128, size // 2)
    measure: bool = True               # stream per-sweep (m, E) + moments
    measure_every: int = 1             # moment-accumulation thinning cadence
    field: float = 0.0                 # external field h (2-D xla only)
    hot: Optional[bool] = None         # None -> hot above Tc, cold below

    def resolved_width(self) -> int:
        return self.width or self.size

    def resolved_block_size(self) -> int:
        return self.block_size or min(L.MXU_BLOCK,
                                      min(self.size, self.resolved_width())
                                      // 2)

    def n_replicas(self) -> int:
        return len(self.betas)

    def resolved_q(self) -> int:
        """Number of Potts states (2 when unset — the Ising-equivalent)."""
        return self.q or 2

    def probs_rule(self) -> str:
        """update_rules name for float-uniform (paper pipeline) paths."""
        return "heat_bath" if self.rule == "heat_bath" else self.accept

    def kernel_rule(self) -> str:
        """update_rules name compiled into the Pallas/ref kernels."""
        return ("heat_bath" if self.rule == "heat_bath"
                else "metropolis_lut")

    def validate(self) -> None:
        err = _config_error
        if (self.beta is None) == (not self.betas):
            err("set exactly one of beta (single chain) or betas "
                f"(replica ensemble); got beta={self.beta!r} "
                f"betas={self.betas!r}")
        if self.dims not in (2, 3):
            err(f"dims must be 2 or 3, got {self.dims}")
        if self.model not in _MODELS:
            err(f"model must be one of {_MODELS}, got {self.model!r}")
        if self.model == "potts":
            if self.q < 2:
                err(f"model='potts' needs q >= 2, got q={self.q}")
            if self.q > 256:
                err(f"q={self.q} overflows the 32-bit fixed-point colour "
                    "draws ((u24 * q) >> 24 needs q <= 256); use a wider "
                    "hash before raising the cap")
            if self.dims != 2:
                err("model='potts' is 2-D only")
            if self.backend != "xla":
                err("model='potts' runs on backend='xla' (the kernel "
                    f"stack is Ising-only); got {self.backend!r}")
            if self.pipeline != "paper":
                err("model='potts' has no separate opt pipeline "
                    "(acceptance is already integer-exact); "
                    "pipeline must be 'paper'")
            if self.ensemble != "independent":
                err("parallel tempering is Ising-only; model='potts' "
                    "needs ensemble='independent'")
            if self.field:
                err("model='potts' samples the h=0 Hamiltonian; "
                    "field must be 0")
            if self.topology == "mesh" and self.betas:
                err("potts ensembles are single-device (vmapped); "
                    "use topology='single' for multi-beta potts runs")
        elif self.q:
            err(f"q={self.q} applies to model='potts' only")
        if self.backend not in _BACKENDS:
            err(f"backend must be one of {_BACKENDS}, got {self.backend!r}")
        if self.topology not in _TOPOLOGIES:
            err(f"topology must be one of {_TOPOLOGIES}, "
                f"got {self.topology!r}")
        if self.pipeline not in _PIPELINES:
            err(f"pipeline must be one of {_PIPELINES}, "
                f"got {self.pipeline!r}")
        if self.ensemble not in _ENSEMBLES:
            err(f"ensemble must be one of {_ENSEMBLES}, "
                f"got {self.ensemble!r}")
        if self.rule not in _RULES:
            err(f"rule must be one of {_RULES}, got {self.rule!r}")
        if self.algorithm not in _ALGORITHMS:
            err(f"algorithm must be one of {_ALGORITHMS}, "
                f"got {self.algorithm!r}")
        if self.measure_every < 1:
            err(f"measure_every must be >= 1, got {self.measure_every}")
        if self.algorithm != "metropolis":
            if self.dims == 3:
                err("cluster algorithms are 2-D only (3-D label "
                    "propagation is not implemented)")
            if self.backend != "xla":
                err("cluster algorithms run on backend='xla' (label "
                    "propagation is a fused-array-op plane, not a Pallas "
                    f"kernel); got {self.backend!r}")
            if self.pipeline != "paper":
                err("cluster algorithms have no separate opt pipeline "
                    "(bond thresholds are already integer-exact); "
                    "pipeline must be 'paper'")
            if self.ensemble != "independent":
                err("tempering swap acceptance assumes Metropolis "
                    "dynamics; algorithm must be 'metropolis'")
            if self.rule != "metropolis":
                err("rule= selects single-site dynamics; cluster "
                    "algorithms replace them entirely — leave "
                    "rule='metropolis'")
            if self.field:
                err("cluster algorithms sample the h=0 Hamiltonian "
                    "(FK bond probabilities assume it); field must be 0")
            if self.betas and self.topology == "mesh":
                err("cluster ensembles are single-device (vmapped); "
                    "use topology='single' for multi-beta cluster runs")
        if self.rule == "heat_bath":
            if self.dims == 3:
                err("rule='heat_bath' is 2-D only (the 3-D sampler has no "
                    "registry hook yet)")
            if self.ensemble == "tempering":
                err("tempering runs Metropolis dynamics (swap acceptance "
                    "assumes it); rule must be 'metropolis'")
        if self.dims == 3:
            if self.backend != "xla":
                err("3-D supports only backend='xla' (the kernel stack is "
                    "2-D); got " + repr(self.backend))
            if self.pipeline != "paper" or self.ensemble != "independent":
                err("3-D supports pipeline='paper', ensemble='independent'")
            if self.field:
                err("3-D external field is not implemented")
            if self.width:
                err("3-D lattices are cubic; width applies to 2-D only")
            if self.betas:
                err("3-D ensembles are not implemented (the vmapped "
                    "replica runner sweeps 2-D compact quads); use a "
                    "scalar beta")
        else:
            w = self.resolved_width()
            if self.size % 2 or w % 2:
                err(f"2-D lattice dims must be even, got "
                    f"{self.size}x{w}")
            bs = self.resolved_block_size()
            if (self.size // 2) % bs or (w // 2) % bs:
                err(f"half-lattice {self.size // 2}x{w // 2} must be "
                    f"divisible by block_size {bs}")
        if self.ensemble == "tempering":
            if not self.betas:
                err("ensemble='tempering' needs a betas ladder")
            if (self.topology, self.backend, self.pipeline) != \
                    ("single", "xla", "paper"):
                err("tempering runs on topology='single', backend='xla', "
                    "pipeline='paper'")
            if not self.measure:
                err("tempering always measures (swap decisions need "
                    "energies); set measure=True")
            if self.field:
                err("tempering samples the h=0 Hamiltonian "
                    "(core.tempering has no field term); field must be 0")
        if self.pipeline == "opt":
            if self.accept != "lut":
                err("pipeline='opt' uses the exact integer-threshold LUT; "
                    "accept must be 'lut'")
            if self.field:
                err("pipeline='opt' requires field=0 (the field term "
                    "forces float acceptance)")
            if self.betas:
                err("pipeline='opt' ensembles are not implemented; use "
                    "pipeline='paper' for multi-beta runs")
            if self.backend not in ("xla", "pallas_lines"):
                err("pipeline='opt' runs on backend='xla' or "
                    f"'pallas_lines'; got {self.backend!r}")
        if self.backend in ("pallas", "pallas_lines", "ref"):
            if self.field:
                err(f"backend={self.backend!r} requires field=0 (the "
                    "kernel bakes the 5-entry LUT)")
            if self.accept != "lut":
                err(f"backend={self.backend!r} uses the in-kernel LUT; "
                    "accept must be 'lut'")
            if self.betas:
                err(f"backend={self.backend!r} ensembles are not "
                    "implemented; use backend='xla' for multi-beta runs")
        if self.topology == "mesh":
            if not self.mesh_shape:
                err("topology='mesh' needs mesh_shape, e.g. (2, 2)")
            if len(self.mesh_axes) < 2:
                err("mesh_axes needs at least (row_axis, col_axis); "
                    f"got {self.mesh_axes}")
            if len(self.mesh_shape) != len(self.mesh_axes):
                err(f"mesh_shape {self.mesh_shape} and mesh_axes "
                    f"{self.mesh_axes} must have equal length")
            if self.backend in ("pallas", "ref"):
                err("mesh topology supports backend='xla' (GSPMD/shard_map)"
                    " or 'pallas_lines' (edge-line halo); "
                    f"got {self.backend!r}")
            if self.field:
                err("mesh topology requires field=0")


class EngineConfigError(ValueError):
    """Raised for invalid EngineConfig combinations (clear, actionable)."""


def _config_error(msg: str):
    raise EngineConfigError(f"invalid EngineConfig: {msg}")


def replica_sweep_fns(cfg: EngineConfig):
    """The per-slot sweep family behind every vmapped multi-chain harness.

    Returns ``(one_sweep, one_sweep_measured, rep_args)``:

    * ``one_sweep(state, key, rep_arg, step) -> state`` and
      ``one_sweep_measured(state, key, rep_arg, step) -> (state, (m, e))``
      advance ONE chain by one sweep. Both are vmappable over leading axes
      of ``(state, key, rep_arg, step)`` — a batch of chains with
      independent keys, couplings, and even sweep counters runs in one
      compiled program.
    * ``rep_args(betas)`` maps an f32 coupling vector to the per-slot
      traced sweep argument: beta itself for single-site dynamics, the
      u24 bond-activation threshold for cluster dynamics (the traced
      thresholds are bitwise-equal to the static trace-time tables —
      pinned in ``tests/test_cluster.py`` / ``tests/test_potts.py``).

    RNG contract (what the serving plane builds on): every uniform draw is
    addressed by ``(key, step)`` alone — ``fold_in(key, step)`` /
    ``sweep_probs(key, step)`` counters, never sequentially split state —
    so a chain advanced in chunks with absolute step indices is
    bitwise-identical to one straight run, and the SLOT a batching harness
    assigns a chain to cannot perturb its stream. The ensemble runners
    below and :class:`repro.serve.engine.MCServeEngine` are both call
    sites of this one function, which is what makes the serving plane's
    bitwise batching-independence guarantee structural rather than
    accidental.

    State layouts per scenario family: compact quads ``[4, R, C]`` for 2-D
    Ising checkerboard, the full ``[L, L]`` spin view for Ising cluster
    sweeps, the full ``[H, W]`` int32 colour view for every Potts dynamics,
    and the ``[D, H, W]`` cube for 3-D Metropolis.
    """
    c = cfg
    if c.model == "potts":
        q = c.resolved_q()
        if c.algorithm != "metropolis":
            from repro.potts import bonds as potts_bonds
            from repro.potts import sweep as potts_sweep
            algo = c.algorithm

            def one_sweep(f, k, t, step):
                return potts_sweep.cluster_sweep(
                    f, jax.random.fold_in(k, step), t, q, algo)

            def one_sweep_measured(f, k, t, step):
                return potts_sweep.cluster_sweep_measured(
                    f, jax.random.fold_in(k, step), t, q, algo)

            def rep_args(betas):
                return potts_bonds.bond_threshold_traced(
                    jnp.asarray(betas, jnp.float32))

            return one_sweep, one_sweep_measured, rep_args

        from repro.potts import rules as potts_rules
        rule = c.rule

        def one_sweep(f, k, beta, step):
            return potts_rules.checkerboard_sweep(
                f, jax.random.fold_in(k, step), beta, q, rule)

        def one_sweep_measured(f, k, beta, step):
            return potts_rules.checkerboard_sweep_measured(
                f, jax.random.fold_in(k, step), beta, q, rule)

        return one_sweep, one_sweep_measured, _beta_args

    if c.dims == 3:
        def one_sweep(f, k, beta, step):
            return I3.sweep3d(f, k, step, beta)

        def one_sweep_measured(f, k, beta, step):
            f = I3.sweep3d(f, k, step, beta)
            return f, (jnp.mean(f.astype(jnp.float32)),
                       obs.energy_per_spin3d(f))

        return one_sweep, one_sweep_measured, _beta_args

    if c.algorithm != "metropolis":
        from repro.cluster import bonds as cbonds
        from repro.cluster import sweep as csweep
        algo = c.algorithm

        def one_sweep(f, k, t, step):
            return csweep.cluster_sweep(
                f, jax.random.fold_in(k, step), t, algo)

        def one_sweep_measured(f, k, t, step):
            return csweep.cluster_sweep_measured(
                f, jax.random.fold_in(k, step), t, algo)

        def rep_args(betas):
            return cbonds.bond_threshold_traced(
                jnp.asarray(betas, jnp.float32))

        return one_sweep, one_sweep_measured, rep_args

    bs = c.resolved_block_size()
    pdt = jnp.dtype(c.prob_dtype)
    rule = c.probs_rule()
    field = c.field

    def one_sweep(q, k, beta, step):
        probs = sampler.sweep_probs(k, step, q.shape[1:], pdt)
        return cb.sweep_compact(q, probs, beta, bs, rule, field=field)

    def one_sweep_measured(q, k, beta, step):
        probs = sampler.sweep_probs(k, step, q.shape[1:], pdt)
        return measure.sweep_compact_measured(q, probs, beta, bs, rule,
                                              field=field)

    return one_sweep, one_sweep_measured, _beta_args


def _beta_args(betas):
    """Identity rep_args: dynamics whose traced per-slot argument is beta."""
    return jnp.asarray(betas, jnp.float32)


@dataclasses.dataclass
class EngineResult:
    """What a run hands back.

    state:          final lattice state (layout depends on the scenario —
                    quads [4, R, C], replicas [Rr, 4, R, C], blocked
                    [4, MR, MC, bs, bs] on a mesh, [D, H, W] in 3-D, or
                    int32 colour views [H, W] / [Rr, H, W] / blocked for
                    model="potts")
    magnetization:  per-sweep m, shape [T] or [n_replicas, T] (None when
                    measure=False, or on mesh/opt fori_loop runs which
                    stream moments instead of a series); for Potts runs
                    this channel carries the order parameter
                    (q max_s rho_s - 1)/(q - 1)
    energy:         per-sweep E/spin, same shape (None when unmeasured)
    moments:        streamed running averages over the measured sweeps —
                    dict with m_abs, E, m2, m4, E2, U4, n_samples (scalars, or
                    arrays of shape [n_replicas] for ensembles). Present on
                    every measured run EXCEPT tempering (which reports the
                    per-round |m| series and swap fraction only); for
                    mesh/opt it is the ONLY measurement output (accumulated
                    inside the compiled loop, measure_every thinning — no
                    per-sweep series ever reaches the host).
    extra:          scenario extras (tempering swap fraction, betas, ...)
    """
    state: jax.Array
    magnetization: Optional[jax.Array] = None
    energy: Optional[jax.Array] = None
    moments: Optional[dict] = None
    extra: dict = dataclasses.field(default_factory=dict)


def beta_ladder(t_over_tc_min: float, t_over_tc_max: float, n: int,
                dims: int = 2) -> tuple:
    """n inverse temperatures spanning [t_min, t_max] x Tc, coldest-first
    temperature order (descending beta ladder ends hottest)."""
    tc = (obs.critical_temperature() if dims == 2 else 1.0 / I3.BETA_C_3D)
    if n == 1:
        return (1.0 / (t_over_tc_min * tc),)
    step = (t_over_tc_max - t_over_tc_min) / (n - 1)
    return tuple(1.0 / ((t_over_tc_min + i * step) * tc) for i in range(n))


class IsingEngine:
    """Config-driven dispatcher over every sampler in the repo.

    Usage::

        engine = IsingEngine(EngineConfig(size=256, beta=0.44, n_sweeps=100))
        state = engine.init(jax.random.PRNGKey(0))
        result = engine.run(state, jax.random.PRNGKey(1))

    or in one line: ``result = engine.simulate(seed=0)`` (splits the seed
    into independent init / chain keys).
    """

    def __init__(self, cfg: EngineConfig, mesh=None):
        cfg.validate()
        self.cfg = cfg
        self._runner_cache: dict = {}
        self.mesh = mesh
        if mesh is None and (cfg.topology == "mesh"
                             or (cfg.pipeline == "opt"
                                 and cfg.topology == "single")):
            shape = cfg.mesh_shape or (1,) * len(cfg.mesh_axes)
            from repro.launch.mesh import make_mesh
            self.mesh = make_mesh(shape, cfg.mesh_axes)
        if self.mesh is not None and cfg.topology == "mesh":
            if cfg.betas:
                n_shards = 1
                for a in cfg.replica_axes:
                    n_shards *= self.mesh.shape[a]
                if cfg.n_replicas() % n_shards:
                    _config_error(
                        f"{cfg.n_replicas()} replicas cannot shard evenly "
                        f"over replica_axes {cfg.replica_axes} "
                        f"(size {n_shards}); pad the betas ladder or "
                        "change replica_axes")
            elif cfg.dims == 3:
                from repro.distributed import halo
                d3cfg = self._dist3d_cfg()
                for name, axes in (("depth", d3cfg.depth_axes),
                                   ("row", d3cfg.row_axes),
                                   ("col", d3cfg.col_axes)):
                    n = halo.axis_size(self.mesh, axes)
                    if cfg.size % n:
                        _config_error(
                            f"3-D cube side {cfg.size} does not divide the "
                            f"{name} shard count {n} (mesh_axes "
                            f"{cfg.mesh_axes}); adjust size or mesh_shape")
            elif self._scenario() == "potts_cb_mesh":
                from repro.distributed import halo
                dcfg = self._dist_cfg()
                nrows = halo.axis_size(self.mesh, dcfg.row_axes)
                ncols = halo.axis_size(self.mesh, dcfg.col_axes)
                if cfg.size % nrows or cfg.resolved_width() % ncols:
                    _config_error(
                        f"colour lattice {cfg.size}x{cfg.resolved_width()} "
                        f"does not tile the {nrows}x{ncols} device grid; "
                        "adjust size/width or mesh_shape")
            else:
                from repro.distributed import halo
                dcfg = self._dist_cfg()
                bs = cfg.resolved_block_size()
                mr, mc = cfg.size // 2 // bs, cfg.resolved_width() // 2 // bs
                nrows = halo.axis_size(self.mesh, dcfg.row_axes)
                ncols = halo.axis_size(self.mesh, dcfg.col_axes)
                if mr % nrows or mc % ncols:
                    _config_error(
                        f"blocked lattice grid {mr}x{mc} (block_size {bs}) "
                        f"does not tile the {nrows}x{ncols} device grid; "
                        "adjust size/width or block_size")

    # ------------------------------------------------------------------
    # Scenario predicates
    # ------------------------------------------------------------------

    @property
    def is_ensemble(self) -> bool:
        return bool(self.cfg.betas)

    def _scenario(self) -> str:
        c = self.cfg
        if c.model == "potts":
            if c.algorithm != "metropolis":
                return ("potts_cluster_mesh" if c.topology == "mesh"
                        else "potts_cluster")
            return ("potts_cb_mesh" if c.topology == "mesh"
                    else "potts_cb")
        if c.dims == 3:
            return "mesh3d" if c.topology == "mesh" else "3d"
        if c.algorithm != "metropolis":
            return ("cluster_mesh" if c.topology == "mesh" else "cluster")
        if c.ensemble == "tempering":
            return "tempering"
        if c.topology == "mesh" and not c.betas:
            return "mesh"
        if c.pipeline == "opt":
            return "opt"
        if c.betas:
            return "ensemble"
        if c.backend != "xla":
            return "kernel"
        return "chain"

    # ------------------------------------------------------------------
    # Geometry / distributed plumbing
    # ------------------------------------------------------------------

    def _dist_cfg(self):
        from repro.distributed import ising as dising
        c = self.cfg
        row_axes = (c.mesh_axes[:-1] or c.mesh_axes) if self.mesh else ("data",)
        col_axes = (c.mesh_axes[-1],) if self.mesh else ("model",)
        return dising.DistIsingConfig(
            beta=c.beta, block_size=c.resolved_block_size(),
            row_axes=row_axes, col_axes=col_axes, accept=c.accept,
            backend=("pallas_lines" if c.backend == "pallas_lines"
                     else "xla"),
            prob_dtype=c.prob_dtype, pipeline=c.pipeline, rule=c.rule)

    def _dist3d_cfg(self):
        """3-D decomposition geometry: the mesh axes map onto the cube's
        (D, H, W) right-aligned — a 2-axis mesh shards (H, W) and leaves
        depth whole, a 3-axis mesh (e.g. (pod, data, model)) shards all
        three, so adding pods extends the simulated volume."""
        from repro.distributed import ising3d as d3
        c = self.cfg
        m = c.mesh_axes
        return d3.Dist3DConfig(
            beta=c.beta,
            depth_axes=tuple(m[:-2]),
            row_axes=(m[-2],), col_axes=(m[-1],))

    def lattice_sharding(self):
        """NamedSharding of the blocked mesh state [4, MR, MC, bs, bs]."""
        from repro.distributed import ising as dising
        return dising.lattice_sharding(self.mesh, self._dist_cfg())

    def state_sharding(self):
        """NamedSharding of this scenario's sharded state layout (None for
        single-device scenarios) — what checkpoint restore re-shards with."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        c = self.cfg
        scen = self._scenario()
        if scen == "mesh3d":
            from repro.distributed import ising3d as d3
            return d3.lattice_sharding(self.mesh, self._dist3d_cfg())
        if scen == "potts_cb_mesh":
            dcfg = self._dist_cfg()
            return NamedSharding(self.mesh,
                                 P(dcfg.row_axes, dcfg.col_axes))
        if scen in ("mesh", "opt", "cluster_mesh", "potts_cluster_mesh"):
            return self.lattice_sharding()
        if c.betas and self.mesh is not None and c.topology == "mesh":
            return NamedSharding(self.mesh,
                                 P(c.replica_axes, None, None, None))
        return None

    def _chain_cfg(self, beta=None) -> sampler.ChainConfig:
        c = self.cfg
        return sampler.ChainConfig(
            beta=(c.beta if beta is None else beta), n_sweeps=c.n_sweeps,
            block_size=c.resolved_block_size(), accept=c.probs_rule(),
            dtype=c.dtype, prob_dtype=c.prob_dtype, measure=c.measure,
            field=c.field)

    # ------------------------------------------------------------------
    # State initialization
    # ------------------------------------------------------------------

    def _auto_hot(self, beta: float) -> bool:
        if self.cfg.hot is not None:
            return self.cfg.hot
        if self.cfg.model == "potts":
            from repro.potts import state as potts_state
            beta_c = potts_state.beta_c(self.cfg.resolved_q())
        else:
            beta_c = (I3.BETA_C_3D if self.cfg.dims == 3
                      else 1.0 / obs.critical_temperature())
        return beta < beta_c  # hot start in the disordered phase

    def init(self, key: jax.Array) -> jax.Array:
        """Initial state for this scenario (see EngineResult for layouts).

        Ensembles: replica i is initialized from ``fold_in(key, i)`` —
        matching the chain-key contract, so a sequential rerun of one
        replica reproduces it end to end. Hot/cold starts resolve per
        replica when ``hot=None`` (hot above Tc, cold below — the standard
        burn-in trick on both sides of the transition).

        A sharded state is built by one jitted program whose output is
        already sharded, so no device ever holds the global lattice; the
        values are those of the eager single-device construction.
        """
        sharding = self.state_sharding()
        if sharding is None:
            return self._build_state(key)
        return jax.jit(self._build_state, out_shardings=sharding)(key)

    def _build_state(self, key: jax.Array) -> jax.Array:
        c = self.cfg
        dt = jnp.dtype(c.dtype)
        scen = self._scenario()
        if scen.startswith("potts"):
            return self._init_potts(key)
        if scen in ("3d", "mesh3d"):
            n = c.size
            return (I3.random_lattice3d(key, n, n, n, dt)
                    if self._auto_hot(c.beta)
                    else I3.cold_lattice3d(n, n, n, dt))
        if scen in ("ensemble", "tempering") or (scen == "cluster"
                                                 and c.betas):
            states = [
                sampler.init_state(jax.random.fold_in(key, i), c.size,
                                   c.resolved_width(), dt,
                                   hot=self._auto_hot(b))
                for i, b in enumerate(c.betas)]
            return jnp.stack(states)
        if scen in ("mesh", "opt", "cluster_mesh"):
            w = c.resolved_width()
            full = (L.random_lattice(key, c.size, w, dt)
                    if self._auto_hot(c.beta) else L.cold_lattice(c.size, w, dt))
            return kops._block_quads(L.to_quads(full),
                                     c.resolved_block_size())
        return sampler.init_state(key, c.size, c.resolved_width(), dt,
                                  hot=self._auto_hot(c.beta))

    def _init_potts(self, key: jax.Array) -> jax.Array:
        """Potts colour states: full [H, W] int32 (single device),
        [R, H, W] replica stacks, or blocked [4, MR, MC, bs, bs] on a mesh
        — the same replica/hot-cold conventions as the Ising layouts."""
        from repro.potts import state as potts_state
        c = self.cfg
        q = c.resolved_q()
        h, w = c.size, c.resolved_width()

        def one(k, beta):
            return (potts_state.random_state(k, h, w, q)
                    if self._auto_hot(beta) else potts_state.cold_state(h, w))

        if c.betas:
            return jnp.stack([one(jax.random.fold_in(key, i), b)
                              for i, b in enumerate(c.betas)])
        full = one(key, c.beta)
        if c.topology == "mesh" and c.algorithm != "metropolis":
            return kops._block_quads(L.to_quads(full), c.resolved_block_size())
        return full   # checkerboard keeps the full view, sharded or not

    # ------------------------------------------------------------------
    # Compiled runners (cached per engine)
    # ------------------------------------------------------------------

    def _replica_harness(self, one_sweep, one_sweep_measured, rep_args,
                         pre=None, post=None):
        """Shared R-replica scaffolding for every multi-β runner: replica
        keys from ``fold_in(key, i)``, fori_loop (unmeasured) or scan with
        fused per-sweep (m, E) streaming (measured), [R, T] series out.
        ``rep_args`` is the per-replica sweep argument (β for Metropolis,
        bond threshold for cluster sweeps); ``pre``/``post`` optionally
        convert the state layout around the compiled loop."""
        c = self.cfg
        n_rep = c.n_replicas()
        post = post or (lambda s: s)

        def run(state, key):
            if pre is not None:
                state = pre(state)
            keys = jax.vmap(lambda i: jax.random.fold_in(key, i))(
                jnp.arange(n_rep))

            if not c.measure:
                def body(step, s):
                    return jax.vmap(one_sweep, in_axes=(0, 0, 0, None))(
                        s, keys, rep_args, step)
                final = jax.lax.fori_loop(0, c.n_sweeps, body, state)
                return post(final), None, None

            def body(carry, step):
                q, (m, e) = jax.vmap(
                    one_sweep_measured, in_axes=(0, 0, 0, None))(
                    carry, keys, rep_args, step)
                return q, (m, e)

            final, (ms, es) = jax.lax.scan(body, state,
                                           jnp.arange(c.n_sweeps))
            return post(final), ms.T, es.T  # [R, T]

        return jax.jit(run)

    def _ensemble_runner(self):
        """Jitted R-replica multi-β chain: vmap over replicas, scan over
        sweeps, observables fused into the compiled loop."""
        one_sweep, one_sweep_measured, rep_args = replica_sweep_fns(self.cfg)
        return self._replica_harness(one_sweep, one_sweep_measured,
                                     rep_args(self.cfg.betas))

    def _kernel_runner(self):
        """Pallas / ref backend chain (single device, scalar β).

        Measured runs keep the lattice BLOCKED through the whole scan and
        stream (m, E) via ``measure.blocked_stats`` — one compact-stencil
        nn recompute per sweep instead of the old per-sweep
        ``_unblock_quads`` + ``from_quads`` + roll reconstruction.
        """
        c = self.cfg
        bs = c.resolved_block_size()
        rule = c.kernel_rule()

        def run(state, key):
            if not c.measure:
                final = kops.run_sweeps(state, key, n_sweeps=c.n_sweeps,
                                        beta=c.beta, bs=bs,
                                        backend=c.backend, rule=rule)
                return final, None, None

            def body(carry, step):
                qb = carry
                with jax.named_scope(L.SWEEP):
                    for color in (0, 1):
                        bits = kops.color_bits(key, step, color,
                                               qb.shape[1:])
                        qb = kops.update_color(qb, bits, c.beta, color,
                                               backend=c.backend, rule=rule)
                    return qb, measure.blocked_stats(qb)

            qb0 = kops._block_quads(state, bs)
            qb, (ms, es) = jax.lax.scan(body, qb0, jnp.arange(c.n_sweeps))
            return kops._unblock_quads(qb), ms, es

        return jax.jit(run)

    def _opt_runner(self):
        """Beyond-paper integer-threshold pipeline via distributed.ising
        (trivial 1-device mesh when topology='single'). With measure=True
        the streaming plane accumulates (|m|, E, m2, m4) moments inside
        the same fori_loop — the throughput path is no longer blind."""
        from repro.distributed import ising as dising
        c = self.cfg
        if c.measure:
            runner = dising.make_run_chain_fn(self.mesh, self._dist_cfg(),
                                              c.n_sweeps, c.measure_every)

            def run(state, key):
                final, mom = runner(state, key)
                return final, None, None, mom
            return run
        runner = dising.make_run_sweeps_fn(self.mesh, self._dist_cfg(),
                                           c.n_sweeps)
        return lambda state, key: (runner(state, key), None, None, None)

    def _cluster_runner(self):
        """Swendsen-Wang / Wolff chain on the full [L, L] view.

        Scalar beta: scan of :func:`repro.cluster.sweep.cluster_sweep`
        with a trace-time bond threshold. Multi-beta: vmap over replicas
        with per-replica traced thresholds (bitwise-equal to the static
        ones — see ``cluster.bonds``), same fold_in(key, i) replica-key
        contract as the Metropolis ensemble runner.
        """
        from repro.cluster import bonds as cbonds
        from repro.cluster import sweep as csweep
        c = self.cfg
        algo = c.algorithm

        if not c.betas:
            t24 = cbonds.bond_threshold_u24(c.beta)

            def run(state, key):
                full = L.from_quads(state)
                if not c.measure:
                    def body(step, f):
                        return csweep.cluster_sweep(
                            f, jax.random.fold_in(key, step), t24, algo)
                    final = jax.lax.fori_loop(0, c.n_sweeps, body, full)
                    return L.to_quads(final), None, None

                def body(f, step):
                    return csweep.cluster_sweep_measured(
                        f, jax.random.fold_in(key, step), t24, algo)

                final, (ms, es) = jax.lax.scan(body, full,
                                               jnp.arange(c.n_sweeps))
                return L.to_quads(final), ms, es

            return jax.jit(run)

        one_sweep, one_sweep_measured, rep_args = replica_sweep_fns(c)
        return self._replica_harness(one_sweep, one_sweep_measured,
                                     rep_args(c.betas),
                                     pre=jax.vmap(L.from_quads),
                                     post=jax.vmap(L.to_quads))

    def _cluster_mesh_runner(self, n_sweeps: int, measured: bool = False):
        from repro.cluster import mesh as cmesh
        key_ = ("cluster_mesh", n_sweeps, measured)
        if key_ not in self._runner_cache:
            make = (cmesh.make_cluster_run_fn if measured
                    else cmesh.make_cluster_sweeps_fn)
            args = ((self.cfg.measure_every,) if measured else ())
            self._runner_cache[key_] = make(
                self.mesh, self._dist_cfg(), self.cfg.algorithm,
                n_sweeps, *args)
        return self._runner_cache[key_]

    def _potts_cb_runner(self):
        """Checkerboard Potts chain (heat-bath or Metropolis per ``rule``)
        on the full [H, W] colour view; multi-beta via the shared replica
        harness with traced betas (thresholds rebuilt in-trace, bitwise
        equal to the static tables — see ``potts.rules``)."""
        from repro.potts import rules as potts_rules
        c = self.cfg
        q = c.resolved_q()
        rule = c.rule

        if not c.betas:
            def run(state, key):
                if not c.measure:
                    def body(step, f):
                        return potts_rules.checkerboard_sweep(
                            f, jax.random.fold_in(key, step), c.beta, q,
                            rule)
                    return (jax.lax.fori_loop(0, c.n_sweeps, body, state),
                            None, None)

                def body(f, step):
                    return potts_rules.checkerboard_sweep_measured(
                        f, jax.random.fold_in(key, step), c.beta, q, rule)

                final, (ms, es) = jax.lax.scan(body, state,
                                               jnp.arange(c.n_sweeps))
                return final, ms, es

            return jax.jit(run)

        one_sweep, one_sweep_measured, rep_args = replica_sweep_fns(c)
        return self._replica_harness(one_sweep, one_sweep_measured,
                                     rep_args(c.betas))

    def _potts_cluster_runner(self):
        """Swendsen-Wang / Wolff Potts chain on the full [H, W] colour
        view — same structure as the Ising ``_cluster_runner`` with the
        Potts bond threshold p = 1 - exp(-beta) and per-cluster colour
        draws; multi-beta via traced thresholds."""
        from repro.potts import bonds as potts_bonds
        from repro.potts import sweep as potts_sweep
        c = self.cfg
        q = c.resolved_q()
        algo = c.algorithm

        if not c.betas:
            t24 = potts_bonds.bond_threshold_u24(c.beta)

            def run(state, key):
                if not c.measure:
                    def body(step, f):
                        return potts_sweep.cluster_sweep(
                            f, jax.random.fold_in(key, step), t24, q, algo)
                    return (jax.lax.fori_loop(0, c.n_sweeps, body, state),
                            None, None)

                def body(f, step):
                    return potts_sweep.cluster_sweep_measured(
                        f, jax.random.fold_in(key, step), t24, q, algo)

                final, (ms, es) = jax.lax.scan(body, state,
                                               jnp.arange(c.n_sweeps))
                return final, ms, es

            return jax.jit(run)

        one_sweep, one_sweep_measured, rep_args = replica_sweep_fns(c)
        return self._replica_harness(one_sweep, one_sweep_measured,
                                     rep_args(c.betas))

    def _potts_cluster_mesh_runner(self, n_sweeps: int,
                                   measured: bool = False):
        from repro.potts import mesh as potts_mesh
        key_ = ("potts_cluster_mesh", n_sweeps, measured)
        if key_ not in self._runner_cache:
            make = (potts_mesh.make_potts_run_fn if measured
                    else potts_mesh.make_potts_sweeps_fn)
            args = ((self.cfg.measure_every,) if measured else ())
            self._runner_cache[key_] = make(
                self.mesh, self._dist_cfg(), self.cfg.resolved_q(),
                self.cfg.algorithm, n_sweeps, *args)
        return self._runner_cache[key_]

    def _potts_cb_mesh_runner(self, n_sweeps: int, measured: bool = False):
        from repro.potts import mesh as potts_mesh
        key_ = ("potts_cb_mesh", n_sweeps, measured)
        if key_ not in self._runner_cache:
            make = (potts_mesh.make_potts_cb_run_fn if measured
                    else potts_mesh.make_potts_cb_sweeps_fn)
            args = ((self.cfg.measure_every,) if measured else ())
            self._runner_cache[key_] = make(
                self.mesh, self._dist_cfg(), self.cfg.resolved_q(),
                self.cfg.rule, n_sweeps, *args)
        return self._runner_cache[key_]

    def _mesh3d_runner(self, n_sweeps: int, measured: bool = False):
        from repro.distributed import ising3d as d3
        key_ = ("mesh3d", n_sweeps, measured)
        if key_ not in self._runner_cache:
            make = (d3.make_run_chain_fn if measured
                    else d3.make_run_sweeps_fn)
            args = ((self.cfg.measure_every,) if measured else ())
            self._runner_cache[key_] = make(self.mesh, self._dist3d_cfg(),
                                            n_sweeps, *args)
        return self._runner_cache[key_]

    def _mesh_runner(self, n_sweeps: int, measured: bool = False):
        from repro.distributed import ising as dising
        key_ = ("mesh", n_sweeps, measured)
        if key_ not in self._runner_cache:
            make = (dising.make_run_chain_fn if measured
                    else dising.make_run_sweeps_fn)
            args = ((self.cfg.measure_every,) if measured else ())
            self._runner_cache[key_] = make(self.mesh, self._dist_cfg(),
                                            n_sweeps, *args)
        return self._runner_cache[key_]

    def _runner_3d(self):
        c = self.cfg

        def run(state, key):
            if not c.measure:
                def body(i, f):
                    return I3.sweep3d(f, key, i, c.beta)
                return (jax.lax.fori_loop(0, c.n_sweeps, body, state),
                        None, None)

            def body(carry, step):
                f = I3.sweep3d(carry, key, step, c.beta)
                return f, (jnp.mean(f.astype(jnp.float32)),
                           obs.energy_per_spin3d(f))

            final, (ms, es) = jax.lax.scan(body, state,
                                           jnp.arange(c.n_sweeps))
            return final, ms, es

        return jax.jit(run)

    # ------------------------------------------------------------------
    # Public entry points
    # ------------------------------------------------------------------

    def run(self, state: jax.Array, key: jax.Array) -> EngineResult:
        """Advance ``state`` by ``cfg.n_sweeps`` sweeps under chain ``key``.

        Single-chain XLA runs are bitwise-identical to
        :func:`repro.core.sampler.run_chain`; ensemble replica i is
        bitwise-identical to a single run keyed ``fold_in(key, i)``.

        Under the profiler the call is the host span ``engine.run``
        (arguments ``scenario``, ``sweeps`` and ``sites``, the lattice sites
        one sweep updates). On the chain and mesh paths it nests
        ``engine.launch`` (the chunk program's call returns),
        ``engine.wait`` (its streamed outputs are ready) and
        ``engine.moments`` (the host folds them into ``moments``).
        """
        c = self.cfg
        scen = self._scenario()
        sites = c.size ** 3 if c.dims == 3 else c.size * c.resolved_width()
        with TraceAnnotation("engine.run", scenario=scen, sweeps=c.n_sweeps,
                             sites=sites * max(c.n_replicas(), 1)):
            return self._run(scen, state, key)

    def _run(self, scen: str, state: jax.Array,
             key: jax.Array) -> EngineResult:
        c = self.cfg
        if scen == "tempering":
            return self._run_tempering(state, key)
        if scen == "chain":
            if not c.measure:
                with TraceAnnotation("engine.launch"):
                    return EngineResult(sampler.run_sweeps(
                        state, key, self._chain_cfg()))
            with TraceAnnotation("engine.launch"):
                final, ms, es = sampler.run_chain(state, key,
                                                  self._chain_cfg())
            with TraceAnnotation("engine.wait"):
                ms, es = jax.block_until_ready((ms, es))
            with TraceAnnotation("engine.moments"):
                mom = self._series_moments(ms, es)
            return EngineResult(final, ms, es, mom)
        if scen in self._MESH_SCENARIOS:
            runner = self._mesh_runner_for(scen)
            if not c.measure:
                with TraceAnnotation("engine.launch"):
                    return EngineResult(runner(c.n_sweeps)(state, key))
            with TraceAnnotation("engine.launch"):
                final, mom = runner(c.n_sweeps, measured=True)(state, key)
            with TraceAnnotation("engine.wait"):
                mom = jax.block_until_ready(mom)
            with TraceAnnotation("engine.moments"):
                return EngineResult(final, moments=measure.finalize(mom))
        runner_key = scen
        if runner_key not in self._runner_cache:
            self._runner_cache[runner_key] = {
                "ensemble": self._ensemble_runner,
                "kernel": self._kernel_runner,
                "cluster": self._cluster_runner,
                "opt": self._opt_runner,
                "3d": self._runner_3d,
                "potts_cb": self._potts_cb_runner,
                "potts_cluster": self._potts_cluster_runner,
            }[scen]()
        out = self._runner_cache[runner_key](state, key)
        final, ms, es = out[:3]
        mom = (measure.finalize(out[3]) if len(out) > 3 and out[3] is not None
               else self._series_moments(ms, es))
        extra = ({"betas": c.betas}
                 if c.betas and scen in ("ensemble", "cluster", "potts_cb",
                                         "potts_cluster") else {})
        return EngineResult(final, ms, es, mom, extra)

    def lower(self, state, key) -> jax.stages.Lowered:
        """The chunk program :meth:`run` launches, lowered for ``state`` and
        ``key`` (arrays or ``jax.ShapeDtypeStruct``) and not run: its HLO
        carries the sweep's named scopes (``lattice.SCOPES``) as op names.
        Chain and mesh scenarios."""
        c = self.cfg
        scen = self._scenario()
        if scen == "chain":
            impl = (sampler._run_chain_impl if c.measure
                    else sampler._run_sweeps_impl)
            return impl.lower(state, key, self._chain_cfg())
        if scen == "mesh":
            return self._mesh_runner(c.n_sweeps, measured=c.measure).lower(
                state, key)
        _config_error(f"lower() covers the chain and mesh scenarios, not "
                      f"{scen!r}")

    def _series_moments(self, ms, es) -> Optional[dict]:
        """Moments from an already-streamed per-sweep series (scan paths) —
        same reporting contract as the fori_loop paths that only
        accumulate. None when the run was measurement-free."""
        if ms is None or es is None:
            return None
        return measure.finalize(measure.moments_from_series(
            ms, es, measure_every=self.cfg.measure_every))

    def _run_tempering(self, state: jax.Array,
                       key: jax.Array) -> EngineResult:
        c = self.cfg
        if c.n_sweeps % c.exchange_every:
            _config_error(f"n_sweeps={c.n_sweeps} must be a multiple of "
                          f"exchange_every={c.exchange_every} for tempering")
        tcfg = pt.TemperingConfig(
            betas=c.betas, n_rounds=c.n_sweeps // c.exchange_every,
            exchange_every=c.exchange_every,
            block_size=c.resolved_block_size(), accept=c.accept,
            dtype=c.dtype)
        final, ms, frac = pt.run_tempering(key, c.size, tcfg,
                                           init_replicas=state)
        return EngineResult(final, ms.T, None,
                            extra={"swap_fraction": frac, "betas": c.betas})

    def _mesh_runner_for(self, scen: str):
        return {"mesh": self._mesh_runner,
                "mesh3d": self._mesh3d_runner,
                "potts_cb_mesh": self._potts_cb_mesh_runner,
                "cluster_mesh": self._cluster_mesh_runner,
                "potts_cluster_mesh": self._potts_cluster_mesh_runner,
                }[scen]

    _MESH_SCENARIOS = ("mesh", "mesh3d", "potts_cb_mesh", "cluster_mesh",
                       "potts_cluster_mesh")

    def run_sweeps(self, state: jax.Array, key: jax.Array,
                   n_sweeps: int) -> jax.Array:
        """Measurement-free chunk of any scenario (the checkpoint cadence
        in ``repro.launch.simulate``); returns only the new state.

        Mesh scenarios dispatch straight to their compiled chunk runner;
        single-device and ensemble scenarios run through a cached
        measurement-free sub-engine with ``n_sweeps`` overridden — the
        same compiled programs, so a chunked run is bitwise a straight run
        (restart safety for every checkpointable scenario).
        """
        scen = self._scenario()
        if scen in self._MESH_SCENARIOS:
            return self._mesh_runner_for(scen)(n_sweeps)(state, key)
        if scen == "tempering":
            _config_error("tempering chunks are not supported; use run() "
                          "(swap decisions need the measured energies)")
        key_ = ("chunk_engine", n_sweeps)
        if key_ not in self._runner_cache:
            self._runner_cache[key_] = IsingEngine(
                dataclasses.replace(self.cfg, n_sweeps=n_sweeps,
                                    measure=False), mesh=self.mesh)
        return self._runner_cache[key_].run(state, key).state

    def simulate(self, seed: int = 0) -> EngineResult:
        """One-call convenience: split seed into init/chain keys and run."""
        k_init, k_chain = jax.random.split(jax.random.PRNGKey(seed))
        return self.run(self.init(k_init), k_chain)

    def magnetization(self, state: jax.Array) -> float:
        """Global mean spin of any engine state layout (host scalar)."""
        return float(jnp.mean(state.astype(jnp.float32)))

    def stats(self, state: jax.Array) -> tuple:
        """Exact global (m, E/spin) of a sharded mesh state without
        gathering it — one jitted shard_map psum over the sharded lattice
        (the streaming plane's standalone entry point; supersedes the old
        magnetization-only logging helper). For Potts meshes ``m`` is the
        order parameter and ``E`` the agreement-bond energy."""
        scen = self._scenario()
        if scen not in ("mesh", "opt", "mesh3d", "potts_cb_mesh",
                        "cluster_mesh", "potts_cluster_mesh"):
            _config_error("stats(state) reads the sharded mesh layouts; "
                          "use run() results elsewhere")
        if "global_stats" not in self._runner_cache:
            if scen == "potts_cluster_mesh":
                from repro.potts import mesh as potts_mesh
                self._runner_cache["global_stats"] = potts_mesh.global_stats(
                    self.mesh, self._dist_cfg(), self.cfg.resolved_q())
            elif scen == "potts_cb_mesh":
                from repro.potts import mesh as potts_mesh
                self._runner_cache["global_stats"] = \
                    potts_mesh.cb_global_stats(
                        self.mesh, self._dist_cfg(), self.cfg.resolved_q())
            elif scen == "mesh3d":
                from repro.distributed import ising3d as d3
                self._runner_cache["global_stats"] = d3.global_stats(
                    self.mesh, self._dist3d_cfg())
            else:
                from repro.distributed import ising as dising
                self._runner_cache["global_stats"] = dising.global_stats(
                    self.mesh, self._dist_cfg())
        m, e = self._runner_cache["global_stats"](state)
        return float(m), float(e)

    def state_template(self):
        """``jax.ShapeDtypeStruct`` of this scenario's state layout — what
        checkpoint restore needs (shape + dtype, no allocation)."""
        c = self.cfg
        scen = self._scenario()
        dt = jnp.dtype(c.dtype)
        if scen.startswith("potts"):
            dt = jnp.int32
        if scen in ("3d", "mesh3d"):
            shape = (c.size,) * 3
        elif scen in ("potts_cb", "potts_cb_mesh", "potts_cluster"):
            shape = (c.size, c.resolved_width())
            if c.betas:
                shape = (c.n_replicas(),) + shape
        elif scen in ("mesh", "opt", "cluster_mesh", "potts_cluster_mesh"):
            bs = c.resolved_block_size()
            shape = (4, c.size // 2 // bs, c.resolved_width() // 2 // bs,
                     bs, bs)
        elif c.betas:   # ensemble / tempering / multi-beta cluster: quads
            shape = (c.n_replicas(), 4, c.size // 2,
                     c.resolved_width() // 2)
        else:           # chain / kernel / cluster: compact quads
            shape = (4, c.size // 2, c.resolved_width() // 2)
        return jax.ShapeDtypeStruct(shape, dt)

    def phase_curve(self, key: jax.Array, burnin: int = 0,
                    full_stats: bool = False) -> list:
        """Phase-diagram scan: run the β ensemble once, reduce each
        replica's fused (m, E) streams to the paper's Fig.-4 statistics.
        Replaces the per-temperature Python loop of ``measure_curve`` with
        one compiled multi-β program.

        ``full_stats=True`` adds susceptibility, specific heat, and the
        integrated autocorrelation time — tau costs a host-side loop of
        device syncs per replica, so it is opt-in.
        """
        c = self.cfg
        if not self.is_ensemble or c.ensemble != "independent":
            _config_error("phase_curve needs an independent-replica betas "
                          "ensemble")
        k_init, k_chain = jax.random.split(key)
        res = self.run(self.init(k_init), k_chain)
        rows = []
        n_spins = (c.size ** 3 if c.dims == 3
                   else c.size * c.resolved_width())
        for i, beta in enumerate(c.betas):
            stats = obs.chain_statistics(
                res.magnetization[i], res.energy[i], burnin,
                beta=(beta if full_stats else 0.0),
                n_spins=(n_spins if full_stats else 0))
            stats["T"] = 1.0 / beta
            stats["beta"] = beta
            stats["size"] = c.size
            rows.append(stats)
        return rows
