"""Streaming observable plane: per-sweep (m, E) from quantities the sweep
already computed, plus running-moment accumulation.

The old measurement path reconstructed the full [H, W] lattice from quads
every sweep (``lattice.from_quads`` — a 4-way scatter) and recomputed all
neighbour sums with ``jnp.roll``. This module replaces it with the identity

    E / N  =  -(1/2N) * sum_i sigma_i * nn_i  =  -(1/N) * sum_white sigma_w * nn_w

Every lattice bond joins one black and one white site, so summing
``sigma * nn`` over the white quads alone counts each bond exactly once —
and ``nn(B), nn(C)`` depend only on the black quads, which the white
half-update does not touch. The white half-sweep therefore already holds
the exact neighbour sums of the *post-sweep* state: measurement is two
elementwise multiplies and a reduction, no scatter, no rolls.

Exactness: spins are ±1 and nn in {-4..4}, so every per-site product is a
small integer and the f32 partial sums stay integer-exact up to 2^24 —
meaning the streamed sums are independent of reduction order (block order,
device order, psum association) and bitwise-reproducible across
decompositions for lattices up to ~4M spins (far beyond test scale).

Three consumers, one code path:

* blocked quads on one device (``blocked_stats``, kernel-backend scans);
* ``shard_map`` sub-lattices — pass ``axis_names`` and local sums are
  ``lax.psum``-reduced into exact global scalars;
* the blocked-quad sweep (``sweep_blocked_measured``, and its [4, R, C]
  wrapper ``sweep_compact_measured``) which reuses the white-update nn
  tensors at zero extra matmul cost.

:class:`Moments` accumulates running ``(|m|, E, m^2, m^4, E^2)`` sums with
``measure_every`` thinning inside compiled loops — the paper's Fig.-4
statistics (plus the specific-heat-bearing E^2 and susceptibility-bearing
m^2 fluctuations) stream out of a measurement-free-speed loop without ever
materializing a time series on the host.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import checkerboard as cb
from repro.core import lattice as L


def _psum(x, axis_names):
    if axis_names:
        return lax.psum(x, axis_names)
    return x


# ---------------------------------------------------------------------------
# Per-sweep scalars
# ---------------------------------------------------------------------------


def magnetization_mean(quads, n_spins: int, axis_names=()) -> jax.Array:
    """Global mean spin from any local spin tensor (quads, blocked quads, a
    tuple of quad arrays, ...). ``n_spins`` is the GLOBAL spin count."""
    with jax.named_scope(L.MEASURE):
        if isinstance(quads, (tuple, list)):
            s = sum(jnp.sum(q.astype(jnp.float32)) for q in quads)
        else:
            s = jnp.sum(quads.astype(jnp.float32))
        return _psum(s, axis_names) / jnp.float32(n_spins)


def bond_energy_from_nn(s0: jax.Array, s1: jax.Array, nn0: jax.Array,
                        nn1: jax.Array, n_spins: int,
                        axis_names=()) -> jax.Array:
    """E per spin from one colour's post-flip spins and their nn sums.

    s0/s1: the two updated quads of one colour AFTER the flip; nn0/nn1 the
    neighbour sums used by that half-update (still exact for the new state,
    since they only read the other colour). Each bond counted once:
    E/N = -(sum sigma*nn over one colour) / N.
    """
    with jax.named_scope(L.MEASURE):
        local = (jnp.sum(s0.astype(jnp.float32) * nn0.astype(jnp.float32))
                 + jnp.sum(s1.astype(jnp.float32)
                           * nn1.astype(jnp.float32)))
        return -_psum(local, axis_names) / jnp.float32(n_spins)


def blocked_stats(qb, n_spins: Optional[int] = None, kh=None,
                  edges=None, axis_names=()) -> tuple:
    """(m, E/spin) of blocked quads [4, mr, mc, bs, bs] (stack or 4-tuple)
    without ``from_quads``: one white-colour nn recompute on the compact
    matmul stencil. Used where the update's own nn is out of reach (the
    fused Pallas kernels keep it in VMEM).

    On a mesh pass the halo ``edges`` provider and ``axis_names``;
    ``n_spins`` defaults to the local spin count (single device).
    """
    a, b, c, d = (qb[i] for i in range(4))
    if kh is None:
        kh = L.kernel_compact(a.shape[-1], a.dtype)
    if edges is None:
        edges = cb.default_edges
    if n_spins is None:
        n_spins = 4 * a.size
    with jax.named_scope(L.MEASURE):
        nn_b, nn_c = cb.nn_white(a, b, c, d, kh, edges)
    m = magnetization_mean((a, b, c, d), n_spins, axis_names)
    e = bond_energy_from_nn(b, c, nn_b, nn_c, n_spins, axis_names)
    return m, e


def sweep_blocked_measured(qb, probs, beta, accept: str = "lut",
                           edges=cb.default_edges,
                           field: float = 0.0) -> tuple:
    """One full sweep of the blocked 4-tuple that also streams (m, E/spin)
    — the measured twin of :func:`repro.core.checkerboard.sweep_blocked`,
    bitwise-identical state evolution, zero extra matmuls for the energy (it
    reuses the white half-update's nn tensors). probs: 4 blocked uniform
    planes [black0, black1, white0, white1]."""
    qb = cb.update_color_blocked(qb, probs[0], probs[1], beta, 0, accept,
                                 edges, field)
    qb, (new0, new1, nn0, nn1) = cb.update_color_blocked(
        qb, probs[2], probs[3], beta, 1, accept, edges, field,
        return_stats=True)
    n_spins = sum(q.size for q in qb)
    m = magnetization_mean(qb, n_spins)
    e = bond_energy_from_nn(new0, new1, nn0, nn1, n_spins)
    return qb, (m, e)


def sweep_compact_measured(quads: jax.Array, probs: jax.Array, beta,
                           block_size: int = L.MXU_BLOCK,
                           accept: str = "lut", edges=cb.default_edges,
                           field: float = 0.0) -> tuple:
    """:func:`sweep_blocked_measured` on [4, R, C] quads and uniforms — the
    measured twin of :func:`repro.core.checkerboard.sweep_compact`."""
    qb = L.block_quads(quads, block_size)
    pb = L.block_quads(probs, block_size)
    qb, (m, e) = sweep_blocked_measured(qb, pb, beta, accept, edges, field)
    return L.unblock_quads(qb), (m, e)


# ---------------------------------------------------------------------------
# Running moments
# ---------------------------------------------------------------------------


class Moments(NamedTuple):
    """Running sums of the Fig.-4 statistics (scalars, f32).

    ``n`` counts accumulated samples; ``m_abs``/``m2``/``m4`` are sums of
    |m|, m^2, m^4. The energy stream is **mean-shifted** (Welford-style):
    ``e_ref`` captures the first kept sample as a running reference, and
    ``de``/``de2`` accumulate sums of (E - e_ref) and (E - e_ref)^2. The
    raw-E^2 scheme this replaces rounded each e^2 sample to f32 (~1.2e-7
    relative of E^2 ~ O(1)) while the physical fluctuation
    <E^2> - <E>^2 = C / (beta^2 N) shrinks with system size — beyond
    ~10^6-10^7 spins the specific heat drowned in rounding noise. Shifted,
    each squared sample is O(fluctuation) itself, so the relative rounding
    stays ~1.2e-7 of the *fluctuation* at any lattice size; the subtraction
    E - e_ref is f32-exact near the reference (Sterbenz) and the unshifted
    moments are recovered exactly in the f64 ``finalize``:
    <E> = e_ref + <d>, <E^2> - <E>^2 = <d^2> - <d>^2.

    This is what lets the mesh/opt/kernel fori_loop paths report specific
    heat C = beta^2 N (<E^2> - <E>^2) at production lattice sizes without
    ever keeping a per-sweep E trace — see
    :func:`repro.core.observables.specific_heat_from_moments`.

    The ``c_*`` fields carry Kahan compensation for the value sums: plain
    f32 accumulation stalls once a sum outgrows its per-sweep increment by
    ~2^24 (a few million sweeps — exactly the run lengths the streaming
    plane targets); compensated summation keeps the running error at one
    ulp regardless of chain length. A NamedTuple so it scans/psums/vmaps
    as a pytree.
    """
    n: jax.Array
    m_abs: jax.Array
    m2: jax.Array
    m4: jax.Array
    e_ref: jax.Array
    de: jax.Array
    de2: jax.Array
    c_m_abs: jax.Array
    c_m2: jax.Array
    c_m4: jax.Array
    c_de: jax.Array
    c_de2: jax.Array

N_FIELDS = 12


def init_moments(batch_shape=()) -> Moments:
    z = jnp.zeros(batch_shape, jnp.float32)
    return Moments(*([z] * N_FIELDS))


def _kahan_add(s, c, x):
    """One compensated-summation step: returns (new_sum, new_comp)."""
    y = x - c
    t = s + y
    return t, (t - s) - y


def accumulate(mom: Moments, m: jax.Array, e: jax.Array,
               step=None, measure_every: int = 1,
               burnin: int = 0) -> Moments:
    """Add one sweep's (m, e) sample, thinned to ``measure_every`` and
    skipping the first ``burnin`` sweeps. ``step`` may be a traced loop
    index — thinning is a branch-free weight, fori_loop/scan safe.

    The thinning grid anchors at ``burnin`` (keeps burnin, burnin+every,
    ...), matching :func:`moments_from_series`'s ``[burnin::every]`` slice
    so the fori_loop and series paths select identical samples."""
    m = jnp.asarray(m, jnp.float32)
    e = jnp.asarray(e, jnp.float32)
    w = jnp.float32(1.0)
    if step is not None and (measure_every > 1 or burnin):
        keep = ((step - burnin) % measure_every == 0) & (step >= burnin)
        w = keep.astype(jnp.float32)
    # The first KEPT sample becomes the running energy reference; every
    # later sample accumulates its (exact, small) deviation from it.
    e_ref = jnp.where((mom.n == 0) & (w > 0), e, mom.e_ref)
    d = e - e_ref
    am = jnp.abs(m)
    s1, c1 = _kahan_add(mom.m_abs, mom.c_m_abs, w * am)
    s2, c2 = _kahan_add(mom.m2, mom.c_m2, w * m * m)
    s3, c3 = _kahan_add(mom.m4, mom.c_m4, w * m ** 4)
    s4, c4 = _kahan_add(mom.de, mom.c_de, w * d)
    s5, c5 = _kahan_add(mom.de2, mom.c_de2, w * d * d)
    # n grows by exact integers: exact in f32 to 2^24 samples, and the
    # f64 finalize below reads it before that matters at realistic
    # measure_every settings.
    return Moments(mom.n + w, s1, s2, s3, e_ref, s4, s5,
                   c1, c2, c3, c4, c5)


def finalize(mom: Moments) -> dict:
    """Host-side reduction of running sums to the Fig.-4 dict (numpy f64;
    the Kahan compensation terms fold back in here and the mean-shifted
    energy stream is unshifted exactly: E = e_ref + <d>,
    E_var = <d^2> - <d>^2, E2 = E_var + E^2).

    Keys match :func:`repro.core.observables.chain_statistics`:
    m_abs, m2, m4, U4, E, E2, E_var, n_samples (E_var feeds
    ``observables.specific_heat_from_moments`` rounding-noise-free at any
    lattice size; E2 is kept for the raw-moment consumers).
    """
    import numpy as np

    def total(s, c):
        return np.asarray(s, np.float64) - np.asarray(c, np.float64)

    n = np.maximum(np.asarray(mom.n, np.float64), 1.0)
    m_abs = total(mom.m_abs, mom.c_m_abs) / n
    m2 = total(mom.m2, mom.c_m2) / n
    m4 = total(mom.m4, mom.c_m4) / n
    d = total(mom.de, mom.c_de) / n
    d2 = total(mom.de2, mom.c_de2) / n
    e = np.asarray(mom.e_ref, np.float64) + d
    e_var = d2 - d ** 2
    u4 = 1.0 - m4 / np.maximum(3.0 * m2 ** 2, 1e-300)
    out = {"m_abs": m_abs, "m2": m2, "m4": m4, "U4": u4, "E": e,
           "E2": e_var + e ** 2, "E_var": e_var,
           "n_samples": np.asarray(mom.n, np.float64)}
    if np.ndim(n) == 0:
        out = {k: (int(v) if k == "n_samples" else float(v))
               for k, v in out.items()}
    return out


def moments_from_series(ms, es, burnin: int = 0,
                        measure_every: int = 1) -> Moments:
    """Fold an already-collected per-sweep series into Moments — keeps the
    scan paths (which stream full series anyway) on the same reporting
    contract as the fori_loop paths that only accumulate. Sums in f64 on
    the host (no compensation needed); the energy reference is the first
    kept sample, matching :func:`accumulate`'s running-reference rule."""
    import numpy as np
    m = np.asarray(ms, np.float64)[..., burnin::measure_every]
    e = np.asarray(es, np.float64)[..., burnin::measure_every]
    n = jnp.asarray(np.full(m.shape[:-1], m.shape[-1], np.float32))
    z = jnp.zeros(m.shape[:-1], jnp.float32)
    e_ref = (e[..., 0] if e.shape[-1]
             else np.zeros(e.shape[:-1], np.float64))
    d = e - e_ref[..., None] if e.shape[-1] else e
    return Moments(n,
                   jnp.asarray(np.abs(m).sum(-1), jnp.float32),
                   jnp.asarray((m * m).sum(-1), jnp.float32),
                   jnp.asarray((m ** 4).sum(-1), jnp.float32),
                   jnp.asarray(e_ref, jnp.float32),
                   jnp.asarray(d.sum(-1), jnp.float32),
                   jnp.asarray((d * d).sum(-1), jnp.float32),
                   z, z, z, z, z)
