"""Cluster-update subsystem (Swendsen-Wang / Wolff via label propagation).

Four layers of pinning, mirroring the repo's testing strategy:

* exactness — label propagation == scipy connected-components oracle;
  integer bond thresholds == f32 probability compares, static == traced;
* algorithm structure — whole clusters flip atomically, Wolff flips
  exactly one, bonds never join antiparallel spins, bond draws are
  decomposition-independent (pure counter RNG);
* engine dispatch — algorithm="swendsen_wang"/"wolff" through IsingEngine,
  ensemble replica-key contract, config validation;
* statistics — SW equilibrium (|m|, E, U4) == Metropolis at several beta
  on 64^2, and the headline: tau_int(|m|) collapse at T_c on 128^2;
* mesh — sharded labels and states bitwise == single-device (subprocess
  with virtual devices, 2x2 shard grid).
"""
from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.cluster import bonds as B
from repro.cluster import label as LBL
from repro.cluster import sweep as CS
from repro.core import lattice as L
from repro.core import observables as obs
from repro.core import sampler


BETA_C = 1.0 / obs.critical_temperature()


# ---------------------------------------------------------------------------
# Label propagation vs scipy oracle
# ---------------------------------------------------------------------------


def _scipy_labels(br: np.ndarray, bd: np.ndarray) -> np.ndarray:
    """Canonical min-index component labels from scipy's csgraph."""
    from scipy.sparse import coo_matrix
    from scipy.sparse.csgraph import connected_components

    h, w = br.shape
    n = h * w
    idx = np.arange(n).reshape(h, w)
    rows, cols = [], []
    for i, j in zip(*np.nonzero(br)):
        rows.append(idx[i, j])
        cols.append(idx[i, (j + 1) % w])
    for i, j in zip(*np.nonzero(bd)):
        rows.append(idx[i, j])
        cols.append(idx[(i + 1) % h, j])
    g = coo_matrix((np.ones(len(rows)), (rows, cols)), shape=(n, n))
    ncomp, comp = connected_components(g, directed=False)
    lab = np.zeros(n, np.int32)
    for c in range(ncomp):
        members = np.nonzero(comp == c)[0]
        lab[members] = members.min()
    return lab.reshape(h, w)


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("p", [0.15, 0.5, 0.85])
def test_label_components_matches_scipy(seed, p):
    rng = np.random.default_rng(seed)
    for h, w in ((12, 12), (8, 20), (16, 8)):
        br = rng.random((h, w)) < p
        bd = rng.random((h, w)) < p
        got = np.asarray(LBL.label_components(jnp.asarray(br),
                                              jnp.asarray(bd)))
        assert (got == _scipy_labels(br, bd)).all(), (seed, p, h, w)


def test_label_no_bonds_every_site_own_cluster():
    z = jnp.zeros((6, 6), bool)
    lab = np.asarray(LBL.label_components(z, z))
    assert (lab == np.arange(36).reshape(6, 6)).all()


def test_label_all_bonds_single_cluster():
    o = jnp.ones((6, 10), bool)
    lab = np.asarray(LBL.label_components(o, o))
    assert (lab == 0).all()


def test_label_snake_worst_case():
    """A serpentine single cluster — the pure-flood worst case; pointer
    jumping must still converge (while_loop makes it exact regardless)."""
    h, w = 8, 8
    br = np.ones((h, w), bool)
    br[:, -1] = False                      # no wrap: rows are segments
    bd = np.zeros((h, w), bool)
    for i in range(h - 1):                 # connect row ends alternately
        bd[i, -1 if i % 2 == 0 else 0] = True
    br[:, :] = br & np.ones((h, w), bool)
    # rows are chains; ends linked -> one serpentine component
    br2 = br.copy()
    lab = np.asarray(LBL.label_components(jnp.asarray(br2),
                                          jnp.asarray(bd)))
    assert (lab == _scipy_labels(br2, bd)).all()
    assert (lab == 0).all()


# ---------------------------------------------------------------------------
# FK bond activation
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("beta", [0.1, 0.3, BETA_C, 0.7, 1.5])
def test_bond_threshold_integer_equals_float_compare(beta):
    """u24 < ceil(p*2^24)  ==  u24/2^24 < p, for the f32 dyadic p."""
    t24 = B.bond_threshold_u24(beta)
    p = np.float32(B.bond_prob_f32(beta))
    bits = np.asarray(
        jax.random.bits(jax.random.PRNGKey(0), (4096,), jnp.uint32))
    u24 = bits >> 8
    int_dec = u24 < t24
    float_dec = (u24.astype(np.float32) * np.float32(2.0 ** -24)) < p
    assert (int_dec == float_dec).all()


def test_bond_threshold_traced_equals_static():
    betas = [0.05, 0.1, 0.25, BETA_C, 0.6, 1.0, 2.0, 5.0]
    traced = np.asarray(jax.jit(B.bond_threshold_traced)(
        jnp.asarray(betas, jnp.float32)))
    static = np.asarray([B.bond_threshold_u24(b) for b in betas])
    assert (traced == static).all()


def test_bonds_only_between_parallel_spins():
    key = jax.random.PRNGKey(1)
    full = L.random_lattice(key, 32, 32, jnp.float32)
    br, bd = B.fk_bonds(full, key, B.bond_threshold_u24(5.0))  # p ~ 1
    f = np.asarray(full)
    east = np.roll(f, -1, 1)
    south = np.roll(f, -1, 0)
    assert (np.asarray(br) <= (f == east)).all()
    assert (np.asarray(bd) <= (f == south)).all()
    # at p ~ 1 every parallel pair IS bonded
    assert (np.asarray(br) == (f == east)).all()


def test_bond_rate_matches_probability():
    beta = 0.4
    p = B.bond_prob_f32(beta)
    key = jax.random.PRNGKey(2)
    full = jnp.ones((64, 64), jnp.float32)   # all parallel
    br, bd = B.fk_bonds(full, key, B.bond_threshold_u24(beta))
    n = 2 * 64 * 64
    rate = (np.asarray(br).sum() + np.asarray(bd).sum()) / n
    sigma = np.sqrt(p * (1 - p) / n)
    assert abs(rate - p) < 5 * sigma, (rate, p)


def test_bonds_decomposition_independent():
    """A sub-patch with global offsets draws exactly the bonds the full
    lattice draws there — the counter-RNG property the mesh relies on."""
    key = jax.random.PRNGKey(3)
    full = L.random_lattice(key, 16, 24, jnp.float32)
    t24 = B.bond_threshold_u24(0.5)
    br, bd = B.fk_bonds(full, key, t24)
    r0, r1, c0, c1 = 4, 12, 8, 24
    patch = full[r0:r1, c0:c1]
    east = jnp.roll(full, -1, 1)[r0:r1, c0:c1]
    south = jnp.roll(full, -1, 0)[r0:r1, c0:c1]
    gi = B.global_index(r1 - r0, c1 - c0, r0, c0, 24)
    brp, bdp = B.fk_bonds(patch, key, t24, east=east, south=south, gi=gi)
    assert (np.asarray(brp) == np.asarray(br)[r0:r1, c0:c1]).all()
    assert (np.asarray(bdp) == np.asarray(bd)[r0:r1, c0:c1]).all()


# ---------------------------------------------------------------------------
# Sweep structure
# ---------------------------------------------------------------------------


def test_sw_flips_whole_clusters():
    key = jax.random.PRNGKey(4)
    full = L.random_lattice(key, 32, 32, jnp.float32)
    t24 = B.bond_threshold_u24(BETA_C)
    skey = jax.random.PRNGKey(5)
    lab = np.asarray(CS.labels_for(full, skey, t24))
    new = np.asarray(CS.cluster_sweep(full, skey, t24, "swendsen_wang"))
    flipped = new != np.asarray(full)
    for root in np.unique(lab):
        sites = lab == root
        assert flipped[sites].all() or (~flipped[sites]).all(), root
    assert flipped.any() and (~flipped).any()  # a fair coin flips ~half


def test_wolff_flips_exactly_one_cluster():
    key = jax.random.PRNGKey(6)
    full = L.random_lattice(key, 32, 32, jnp.float32)
    t24 = B.bond_threshold_u24(BETA_C)
    skey = jax.random.PRNGKey(7)
    lab = np.asarray(CS.labels_for(full, skey, t24))
    new = np.asarray(CS.cluster_sweep(full, skey, t24, "wolff"))
    flipped = new != np.asarray(full)
    roots = np.unique(lab[flipped])
    assert roots.size == 1                       # one cluster flipped ...
    assert flipped[lab == roots[0]].all()        # ... in its entirety


def test_cluster_sweep_measured_matches_observables():
    key = jax.random.PRNGKey(8)
    full = L.random_lattice(key, 32, 32, jnp.float32)
    t24 = B.bond_threshold_u24(0.6)
    new, (m, e) = CS.cluster_sweep_measured(full, key, t24)
    quads = L.to_quads(new)
    assert float(m) == pytest.approx(float(obs.magnetization(quads)), abs=0)
    assert float(e) == pytest.approx(float(obs.energy_per_spin(quads)),
                                     abs=1e-6)


def test_cluster_sweep_deterministic():
    key = jax.random.PRNGKey(9)
    full = L.random_lattice(key, 16, 16, jnp.float32)
    t24 = B.bond_threshold_u24(0.5)
    a = np.asarray(CS.cluster_sweep(full, key, t24))
    b = np.asarray(CS.cluster_sweep(full, key, t24))
    assert (a == b).all()


# ---------------------------------------------------------------------------
# Engine dispatch
# ---------------------------------------------------------------------------


def test_engine_sw_runs_and_streams():
    from repro.api import EngineConfig, IsingEngine
    eng = IsingEngine(EngineConfig(size=32, beta=0.5, n_sweeps=15,
                                   algorithm="swendsen_wang",
                                   dtype="float32"))
    res = eng.simulate(seed=0)
    assert res.state.shape == (4, 16, 16)
    assert res.magnetization.shape == (15,)
    assert res.energy.shape == (15,)
    assert res.moments is not None and res.moments["n_samples"] == 15
    assert -2.0 <= res.moments["E"] <= 0.0
    assert 0.0 <= res.moments["m_abs"] <= 1.0


def test_engine_wolff_runs():
    from repro.api import EngineConfig, IsingEngine
    eng = IsingEngine(EngineConfig(size=32, beta=BETA_C, n_sweeps=10,
                                   algorithm="wolff"))
    res = eng.simulate(seed=1)
    assert res.magnetization.shape == (10,)


def test_engine_cluster_measure_false():
    from repro.api import EngineConfig, IsingEngine
    eng = IsingEngine(EngineConfig(size=32, beta=0.5, n_sweeps=5,
                                   algorithm="swendsen_wang",
                                   measure=False))
    res = eng.simulate(seed=0)
    assert res.magnetization is None and res.moments is None
    assert res.state.shape == (4, 16, 16)


def test_engine_cluster_ensemble_replica_contract():
    """Cluster-ensemble replica i is bitwise a single chain keyed
    fold_in(key, i) — the engine-wide RNG contract, now for SW."""
    from repro.api import EngineConfig, IsingEngine
    betas = (0.35, BETA_C, 0.55)
    eng = IsingEngine(EngineConfig(size=16, betas=betas, n_sweeps=8,
                                   algorithm="swendsen_wang",
                                   dtype="float32"))
    key = jax.random.PRNGKey(11)
    k_init, k_chain = jax.random.split(key)
    res = eng.run(eng.init(k_init), k_chain)
    assert res.magnetization.shape == (3, 8)
    assert res.extra["betas"] == betas
    for i, b in enumerate(betas):
        one = IsingEngine(EngineConfig(
            size=16, beta=b, n_sweeps=8, algorithm="swendsen_wang",
            dtype="float32", hot=bool(eng._auto_hot(b))))
        r1 = one.run(one.init(jax.random.fold_in(k_init, i)),
                     jax.random.fold_in(k_chain, i))
        assert (np.asarray(r1.state) == np.asarray(res.state[i])).all(), i
        assert np.array_equal(np.asarray(r1.magnetization),
                              np.asarray(res.magnetization[i])), i


@pytest.mark.parametrize("overrides", [
    dict(algorithm="swendsen_wang", backend="pallas"),
    dict(algorithm="swendsen_wang", backend="ref"),
    dict(algorithm="wolff", dims=3),
    dict(algorithm="swendsen_wang", rule="heat_bath"),
    dict(algorithm="swendsen_wang", pipeline="opt"),
    dict(algorithm="swendsen_wang", field=0.1),
    dict(algorithm="no_such_algo"),
])
def test_engine_cluster_config_errors(overrides):
    from repro.api import EngineConfig, IsingEngine
    from repro.api.engine import EngineConfigError
    kw = dict(size=32, beta=0.5)
    kw.update(overrides)
    with pytest.raises(EngineConfigError):
        IsingEngine(EngineConfig(**kw))


def test_engine_cluster_tempering_rejected():
    from repro.api import EngineConfig, IsingEngine
    from repro.api.engine import EngineConfigError
    with pytest.raises(EngineConfigError):
        IsingEngine(EngineConfig(size=32, betas=(0.4, 0.5),
                                 algorithm="wolff", ensemble="tempering"))


# ---------------------------------------------------------------------------
# Equilibrium: SW == Metropolis (statistical)
# ---------------------------------------------------------------------------


def _binned_stats(ms, es, nbins=8):
    """Per-bin (|m|, E, U4) means -> (means, stderr) over bins."""
    m = np.abs(np.asarray(ms, np.float64))
    e = np.asarray(es, np.float64)
    n = (m.shape[0] // nbins) * nbins
    mb = m[:n].reshape(nbins, -1)
    eb = e[:n].reshape(nbins, -1)
    m2 = (mb ** 2).mean(1)
    m4 = (mb ** 4).mean(1)
    u4 = 1.0 - m4 / np.maximum(3.0 * m2 ** 2, 1e-300)
    vals = np.stack([mb.mean(1), eb.mean(1), u4])       # [3, nbins]
    return vals.mean(1), vals.std(1, ddof=1) / np.sqrt(nbins)


@pytest.mark.statistical
@pytest.mark.parametrize("beta_factor", [0.9, 1.0, 1.1])
def test_sw_equilibrium_matches_metropolis_64(beta_factor):
    """|m|, E, U4 agree between SW and Metropolis on 64^2 within combined
    binned stderr — same Boltzmann measure, different dynamics.

    Tolerance: 5 sigma of the combined binned stderr (binning absorbs
    autocorrelation) + 0.02 absolute slack for residual finite-chain bias
    near beta_c where tau_int inflates the true error beyond the binned
    estimate. Seeds 42/43 are pinned, so on a fixed jax version this test
    is deterministic; the margin is what makes it survive a jax/XLA bump
    reshuffling the underlying streams."""
    from repro.api import EngineConfig, IsingEngine
    beta = beta_factor * BETA_C
    size = 64

    eng_m = IsingEngine(EngineConfig(size=size, beta=beta, n_sweeps=4000,
                                     dtype="float32"))
    res_m = eng_m.simulate(seed=42)
    ref, se_ref = _binned_stats(res_m.magnetization[800:],
                                res_m.energy[800:])

    eng_c = IsingEngine(EngineConfig(size=size, beta=beta, n_sweeps=900,
                                     algorithm="swendsen_wang",
                                     dtype="float32"))
    res_c = eng_c.simulate(seed=43)
    got, se_got = _binned_stats(res_c.magnetization[100:],
                                res_c.energy[100:])

    se = np.sqrt(se_ref ** 2 + se_got ** 2)
    for name, r, g, s in zip(("m_abs", "E", "U4"), ref, got, se):
        assert abs(r - g) < 5 * s + 0.02, (
            f"{name} at beta={beta_factor}*beta_c: metropolis={r:.4f} "
            f"sw={g:.4f} tol={5 * s + 0.02:.4f}")


@pytest.mark.statistical
def test_tau_collapse_at_tc_128():
    """The headline: tau_int(|m|) at T_c on 128^2 is >= 5x smaller for
    Swendsen-Wang than for checkerboard Metropolis.

    Thresholds: physics predicts tau_SW = O(1) vs tau_Metropolis ~ L^2.15
    (>> 100 at L=128), so the 5x ratio floor and tau_c < 20 ceiling sit an
    order of magnitude inside the expected gap — loose enough that the
    windowed tau estimator's bias on pinned seeds 7/8 cannot cross them."""
    from repro.api import EngineConfig, IsingEngine

    eng_m = IsingEngine(EngineConfig(size=128, beta=BETA_C, n_sweeps=6000,
                                     dtype="float32", hot=True))
    res_m = eng_m.simulate(seed=7)
    tau_m, w_m = obs.autocorrelation(
        np.abs(np.asarray(res_m.magnetization, np.float64))[500:])

    eng_c = IsingEngine(EngineConfig(size=128, beta=BETA_C, n_sweeps=1200,
                                     algorithm="swendsen_wang",
                                     dtype="float32", hot=True))
    res_c = eng_c.simulate(seed=8)
    tau_c, w_c = obs.autocorrelation(
        np.abs(np.asarray(res_c.magnetization, np.float64))[200:])

    assert tau_c < 20, f"SW tau unexpectedly large: {tau_c} (window {w_c})"
    ratio = tau_m / tau_c
    assert ratio >= 5.0, (
        f"tau collapse too weak: metropolis={tau_m:.1f} (window {w_m}) "
        f"sw={tau_c:.1f} (window {w_c}) ratio={ratio:.2f}")


# ---------------------------------------------------------------------------
# Mesh path == single device, bitwise (subprocess, virtual devices)
# ---------------------------------------------------------------------------


def test_mesh_labels_and_states_bitwise_single(subproc):
    out = subproc("""
    import numpy as np, jax, jax.numpy as jnp
    from repro.launch.mesh import make_mesh
    from repro.distributed import ising as dising
    from repro.core import lattice as L, measure
    from repro.cluster import mesh as cmesh, sweep as csweep, bonds as B

    mesh = make_mesh((2, 2), ("data", "model"))
    beta, bs, mr, mc = 0.45, 8, 4, 4          # 64x64 lattice, 2x2 shards
    cfg = dising.DistIsingConfig(beta=beta, block_size=bs,
                                 row_axes=("data",), col_axes=("model",))
    key = jax.random.PRNGKey(3)
    full = L.random_lattice(key, 2*mr*bs, 2*mc*bs, jnp.bfloat16)
    quads = L.to_quads(full)
    qb = jnp.stack([L.block(quads[i], bs) for i in range(4)])
    qb_sh = jax.device_put(qb, dising.lattice_sharding(mesh, cfg))
    skey = jax.random.PRNGKey(7)

    # labels: sharded == single device, exactly (canonical min labels)
    lab_mesh = np.asarray(jax.device_get(
        cmesh.make_labels_fn(mesh, cfg)(qb_sh, skey)))
    t24 = B.bond_threshold_u24(beta)
    lab_single = np.asarray(csweep.labels_for(full, skey, t24))
    assert (lab_mesh == lab_single).all(), "mesh labels != single"

    # a 6-sweep SW chain: states bitwise equal
    runner = cmesh.make_cluster_run_fn(mesh, cfg, "swendsen_wang", 6)
    qb_out, mom = runner(qb_sh, skey)
    f = full
    for step in range(6):
        f = csweep.cluster_sweep(f, jax.random.fold_in(skey, step), t24)
    q = L.to_quads(f)
    qb_ref = jnp.stack([L.block(q[i], bs) for i in range(4)])
    assert (np.asarray(jax.device_get(qb_out))
            == np.asarray(qb_ref)).all(), "mesh state != single"
    fin = measure.finalize(jax.device_get(mom))
    assert fin["n_samples"] == 6 and -2.0 <= fin["E"] <= 0.0

    # wolff too
    qb_sh2 = jax.device_put(qb, dising.lattice_sharding(mesh, cfg))
    qb_w, _ = cmesh.make_cluster_run_fn(mesh, cfg, "wolff", 4)(qb_sh2, skey)
    fw = full
    for step in range(4):
        fw = csweep.cluster_sweep(fw, jax.random.fold_in(skey, step), t24,
                                  "wolff")
    qw = L.to_quads(fw)
    qbw = jnp.stack([L.block(qw[i], bs) for i in range(4)])
    assert (np.asarray(jax.device_get(qb_w)) == np.asarray(qbw)).all()
    print("CLUSTER_MESH_BITWISE_OK")
    """, devices=4)
    assert "CLUSTER_MESH_BITWISE_OK" in out


def test_mesh_engine_cluster_moments(subproc):
    out = subproc("""
    import jax
    from repro.api import EngineConfig, IsingEngine
    eng = IsingEngine(EngineConfig(size=32, beta=0.5, n_sweeps=8,
                                   algorithm="swendsen_wang",
                                   topology="mesh", mesh_shape=(2, 2),
                                   mesh_axes=("data", "model"),
                                   block_size=8))
    res = eng.simulate(seed=0)
    mom = res.moments
    assert mom["n_samples"] == 8
    assert 0.0 <= mom["m_abs"] <= 1.0 and -2.0 <= mom["E"] <= 0.0
    m, e = eng.stats(res.state)
    assert -1.0 <= m <= 1.0 and -2.0 <= e <= 0.0
    st = eng.init(jax.random.PRNGKey(0))
    st = eng.run_sweeps(st, jax.random.PRNGKey(1), 3)
    assert st.shape == (4, 2, 2, 8, 8)
    print("CLUSTER_MESH_ENGINE_OK")
    """, devices=4)
    assert "CLUSTER_MESH_ENGINE_OK" in out


def test_mesh_1d_row_decomposition_bitwise(subproc):
    """A 4x1 device grid (rows only; column wrap stays local)."""
    out = subproc("""
    import numpy as np, jax, jax.numpy as jnp
    from repro.launch.mesh import make_mesh
    from repro.distributed import ising as dising
    from repro.core import lattice as L
    from repro.cluster import mesh as cmesh, sweep as csweep, bonds as B

    mesh = make_mesh((4, 1), ("data", "model"))
    beta, bs, mr, mc = 0.5, 4, 4, 2
    cfg = dising.DistIsingConfig(beta=beta, block_size=bs,
                                 row_axes=("data",), col_axes=("model",))
    key = jax.random.PRNGKey(5)
    full = L.random_lattice(key, 2*mr*bs, 2*mc*bs, jnp.float32)
    quads = L.to_quads(full)
    qb = jnp.stack([L.block(quads[i], bs) for i in range(4)])
    qb_sh = jax.device_put(qb, dising.lattice_sharding(mesh, cfg))
    skey = jax.random.PRNGKey(6)
    lab_mesh = np.asarray(jax.device_get(
        cmesh.make_labels_fn(mesh, cfg)(qb_sh, skey)))
    lab_single = np.asarray(csweep.labels_for(
        full, skey, B.bond_threshold_u24(beta)))
    assert (lab_mesh == lab_single).all()
    print("CLUSTER_1D_OK")
    """, devices=4)
    assert "CLUSTER_1D_OK" in out
