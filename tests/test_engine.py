"""`IsingEngine` front door: dispatch parity, β-ensembles, config errors.

The engine's contract (module docstring of repro.api.engine):

* single-device scalar-β XLA runs are BITWISE-identical to driving
  `core.sampler` / `core.checkerboard` directly with the same key;
* ensemble replica i is BITWISE-identical to a single run keyed
  ``fold_in(key, i)``;
* invalid configuration combinations raise `EngineConfigError` with an
  actionable message.
"""
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import EngineConfig, IsingEngine, beta_ladder
from repro.api.engine import EngineConfigError
from repro.core import checkerboard as cb
from repro.core import sampler

SIZE, BLOCK, SWEEPS = 32, 8, 5
BETA = 0.4406868


def test_engine_matches_direct_checkerboard_bitwise():
    """(a) engine sweeps == hand-driven core.checkerboard, same key."""
    key = jax.random.PRNGKey(0)
    engine = IsingEngine(EngineConfig(size=SIZE, beta=BETA, n_sweeps=SWEEPS,
                                      block_size=BLOCK, hot=True))
    state = engine.init(key)
    res = engine.run(state, key)

    q = sampler.init_state(key, SIZE, SIZE, hot=True)
    for step in range(SWEEPS):
        probs = sampler.sweep_probs(key, step, q.shape[1:], jnp.float32)
        q = cb.sweep_compact(q, probs, BETA, BLOCK, "lut")
    np.testing.assert_array_equal(np.asarray(res.state), np.asarray(q))


def test_engine_matches_sampler_run_chain():
    key = jax.random.PRNGKey(3)
    engine = IsingEngine(EngineConfig(size=SIZE, beta=BETA, n_sweeps=SWEEPS,
                                      block_size=BLOCK, hot=True))
    res = engine.run(engine.init(key), key)
    ccfg = sampler.ChainConfig(beta=BETA, n_sweeps=SWEEPS, block_size=BLOCK)
    final, ms, es = sampler.run_chain(
        sampler.init_state(key, SIZE, SIZE, hot=True), key, ccfg)
    np.testing.assert_array_equal(np.asarray(res.state), np.asarray(final))
    np.testing.assert_array_equal(np.asarray(res.magnetization),
                                  np.asarray(ms))
    np.testing.assert_array_equal(np.asarray(res.energy), np.asarray(es))


def test_ensemble_matches_sequential_runs():
    """(b) the vmapped 4-replica β-ensemble == 4 sequential single-β runs
    (states bitwise, observables bitwise)."""
    key = jax.random.PRNGKey(1)
    betas = beta_ladder(0.8, 1.2, 4)
    eng = IsingEngine(EngineConfig(size=SIZE, betas=betas, n_sweeps=SWEEPS,
                                   block_size=BLOCK))
    res = eng.run(eng.init(key), key)
    assert res.state.shape[0] == 4
    assert res.magnetization.shape == (4, SWEEPS)
    assert res.energy.shape == (4, SWEEPS)

    for i, beta in enumerate(betas):
        ki = jax.random.fold_in(key, i)
        single = IsingEngine(EngineConfig(
            size=SIZE, beta=beta, n_sweeps=SWEEPS, block_size=BLOCK,
            hot=eng._auto_hot(beta)))
        sres = single.run(single.init(ki), ki)
        np.testing.assert_array_equal(np.asarray(res.state[i]),
                                      np.asarray(sres.state))
        np.testing.assert_array_equal(np.asarray(res.magnetization[i]),
                                      np.asarray(sres.magnetization))


def test_ensemble_measure_free_matches_measured_final_state():
    key = jax.random.PRNGKey(2)
    betas = beta_ladder(0.9, 1.1, 3)
    kw = dict(size=SIZE, betas=betas, n_sweeps=SWEEPS, block_size=BLOCK)
    meas = IsingEngine(EngineConfig(**kw))
    fast = IsingEngine(EngineConfig(measure=False, **kw))
    r1 = meas.run(meas.init(key), key)
    r2 = fast.run(fast.init(key), key)
    assert r2.magnetization is None and r2.energy is None
    np.testing.assert_array_equal(np.asarray(r1.state), np.asarray(r2.state))


def test_phase_curve_one_call():
    rows = IsingEngine(EngineConfig(
        size=16, betas=beta_ladder(0.7, 1.3, 3), n_sweeps=40,
        block_size=4)).phase_curve(jax.random.PRNGKey(0), burnin=10,
                                   full_stats=True)
    assert len(rows) == 3
    for r in rows:
        assert set(r) >= {"m_abs", "U4", "E", "T", "beta", "chi", "C"}
    # coldest point should be clearly more ordered than the hottest
    assert rows[0]["m_abs"] > rows[-1]["m_abs"]
    # default (fast) path skips the host-loop extras
    fast = IsingEngine(EngineConfig(
        size=16, betas=beta_ladder(0.7, 1.3, 3), n_sweeps=40,
        block_size=4)).phase_curve(jax.random.PRNGKey(0), burnin=10)
    assert "chi" not in fast[0] and "tau_m" not in fast[0]


def test_kernel_backend_dispatch():
    """ref backend == pallas interpret backend (bitwise kernel contract),
    both reachable through the engine."""
    key = jax.random.PRNGKey(4)
    out = {}
    for backend in ("ref", "pallas"):
        eng = IsingEngine(EngineConfig(size=SIZE, beta=BETA, n_sweeps=2,
                                       block_size=BLOCK, backend=backend,
                                       hot=True))
        out[backend] = np.asarray(eng.run(eng.init(key), key).state)
    np.testing.assert_array_equal(out["ref"], out["pallas"])


def test_engine_3d_dispatch():
    eng = IsingEngine(EngineConfig(size=8, beta=1.5 * 0.2216546,
                                   n_sweeps=10, dims=3))
    res = eng.simulate(seed=0)
    assert res.state.shape == (8, 8, 8)
    assert res.magnetization.shape == (10,)
    assert float(jnp.abs(res.magnetization[-1])) > 0.5  # ordered phase


def test_engine_tempering_dispatch():
    eng = IsingEngine(EngineConfig(
        size=16, betas=beta_ladder(0.6, 1.6, 3), ensemble="tempering",
        n_sweeps=20, exchange_every=5, block_size=4, hot=True))
    res = eng.simulate(seed=0)
    assert res.magnetization.shape == (3, 4)  # [R, rounds]
    assert "swap_fraction" in res.extra


def test_opt_pipeline_single_device():
    eng = IsingEngine(EngineConfig(size=SIZE, beta=BETA, n_sweeps=3,
                                   block_size=BLOCK, pipeline="opt",
                                   measure=False, hot=True))
    res = eng.run(eng.init(jax.random.PRNGKey(0)), jax.random.PRNGKey(0))
    assert res.state.shape == (4, 2, 2, BLOCK, BLOCK)
    assert set(np.unique(np.asarray(res.state, np.float32))) <= {-1.0, 1.0}


def test_opt_pipeline_streams_moments():
    """pipeline='opt' + measure=True (now legal): running (|m|, E, m2, m4)
    moments accumulate inside the compiled loop; with one sweep they match
    the oracle observables of the returned final state exactly (the
    streamed sums are integer-exact in f32)."""
    from repro.core import lattice as L
    from repro.core import observables as obs

    key = jax.random.PRNGKey(0)
    eng = IsingEngine(EngineConfig(size=SIZE, beta=BETA, n_sweeps=1,
                                   block_size=BLOCK, pipeline="opt",
                                   measure=True, hot=True))
    res = eng.run(eng.init(key), key)
    assert res.magnetization is None            # fori_loop path: no series
    mom = res.moments
    assert mom["n_samples"] == 1
    state = jnp.asarray(jax.device_get(res.state))
    quads = jnp.stack([L.unblock(state[i]) for i in range(4)])
    assert mom["E"] == float(obs.energy_per_spin(quads))
    assert mom["m_abs"] == abs(float(obs.magnetization(quads)))


def test_measure_every_thins_moments():
    key = jax.random.PRNGKey(2)
    kw = dict(size=SIZE, beta=BETA, n_sweeps=10, block_size=BLOCK, hot=True)
    full = IsingEngine(EngineConfig(**kw))
    thin = IsingEngine(EngineConfig(measure_every=2, **kw))
    r_full = full.run(full.init(key), key)
    r_thin = thin.run(thin.init(key), key)
    assert r_full.moments["n_samples"] == 10
    assert r_thin.moments["n_samples"] == 5
    # thinned moments == manual slice of the full series
    ms = np.asarray(r_full.magnetization, np.float64)[::2]
    np.testing.assert_allclose(r_thin.moments["m_abs"],
                               np.abs(ms).mean(), rtol=1e-6)


def test_heat_bath_rule_dispatches_every_2d_backend():
    """rule='heat_bath' runs on xla / ref / pallas / pallas_lines and the
    opt pipeline; ref == pallas stays bitwise under the new rule."""
    key = jax.random.PRNGKey(5)
    out = {}
    for backend in ("xla", "ref", "pallas", "pallas_lines"):
        eng = IsingEngine(EngineConfig(size=SIZE, beta=BETA, n_sweeps=2,
                                       block_size=BLOCK, backend=backend,
                                       rule="heat_bath", hot=True))
        res = eng.run(eng.init(key), key)
        state = np.asarray(res.state, np.float32)
        assert set(np.unique(state)) <= {-1.0, 1.0}, backend
        out[backend] = state
        assert res.moments is not None and res.moments["n_samples"] == 2
    np.testing.assert_array_equal(out["ref"], out["pallas"])
    np.testing.assert_array_equal(out["ref"], out["pallas_lines"])
    opt = IsingEngine(EngineConfig(size=SIZE, beta=BETA, n_sweeps=2,
                                   block_size=BLOCK, pipeline="opt",
                                   rule="heat_bath", hot=True))
    r = opt.run(opt.init(key), key)
    assert r.moments["n_samples"] == 2


@pytest.mark.parametrize("bad, hint", [
    (dict(size=32, beta=0.4, betas=(0.4, 0.5)), "exactly one"),
    (dict(size=32), "exactly one"),
    (dict(size=33, beta=0.4), "even"),
    (dict(size=32, beta=0.4, dims=4), "dims"),
    (dict(size=32, beta=0.4, dims=3, backend="pallas"), "3-D"),
    (dict(size=32, beta=0.4, dims=3, width=16), "cubic"),
    (dict(size=32, beta=0.4, topology="mesh"), "mesh_shape"),
    (dict(size=32, betas=(0.3, 0.4), pipeline="opt"), "opt"),
    (dict(size=32, beta=0.4, rule="wolff"), "rule"),
    (dict(size=32, beta=0.4, measure_every=0), "measure_every"),
    (dict(size=8, beta=0.3, dims=3, rule="heat_bath"), "2-D"),
    (dict(size=32, betas=(0.3, 0.4), ensemble="tempering",
          rule="heat_bath"), "Metropolis"),
    (dict(size=32, betas=(0.3, 0.4), ensemble="tempering", field=0.1),
     "h=0"),
    (dict(size=32, beta=0.4, backend="pallas", accept="exp"), "LUT"),
    (dict(size=32, betas=(0.3, 0.4), ensemble="tempering",
          backend="ref"), "tempering"),
    (dict(size=32, beta=0.4, backend="warp"), "backend"),
])
def test_invalid_configs_raise_clear_errors(bad, hint):
    with pytest.raises(EngineConfigError, match="invalid EngineConfig"):
        IsingEngine(EngineConfig(**bad))
    try:
        IsingEngine(EngineConfig(**bad))
    except EngineConfigError as e:
        assert hint.lower() in str(e).lower(), (hint, str(e))


def test_beta_zero_is_legal():
    """β = 0 (infinite temperature, every flip accepted) is a value, not
    'unset' — the free-spin sanity check must construct and run."""
    eng = IsingEngine(EngineConfig(size=16, beta=0.0, n_sweeps=5,
                                   block_size=4, hot=True))
    res = eng.simulate(seed=0)
    # at beta=0 every flip is accepted: a hot lattice inverts site-by-site
    # each sweep and |m| stays at thermal-noise scale
    assert float(jnp.abs(res.magnetization[-1])) < 0.5


def test_mesh_dispatch_and_replica_sharding(subproc):
    """Mesh topology: spatial decomposition runs, and a replica-sharded
    β-ensemble matches the single-device ensemble bitwise."""
    out = subproc("""
    import numpy as np, jax
    from repro.api import IsingEngine, EngineConfig, beta_ladder
    key = jax.random.PRNGKey(0)

    cfg = EngineConfig(size=64, beta=0.4406868, n_sweeps=3, block_size=8,
                       topology="mesh", mesh_shape=(2, 2), measure=False,
                       hot=True)
    eng = IsingEngine(cfg)
    state = eng.init(key)
    assert state.shape == (4, 4, 4, 8, 8)
    res = eng.run(state, key)
    assert abs(eng.magnetization(res.state)) <= 1.0

    # measured mesh run (streaming moments; no series on the fori path)
    mcfg = EngineConfig(size=64, beta=0.4406868, n_sweeps=3, block_size=8,
                        topology="mesh", mesh_shape=(2, 2), measure=True,
                        hot=True)
    meng = IsingEngine(mcfg)
    mres = meng.run(meng.init(key), key)
    assert mres.magnetization is None
    assert mres.moments["n_samples"] == 3
    assert abs(mres.moments["E"]) <= 2.0 and mres.moments["m_abs"] <= 1.0

    betas = beta_ladder(0.8, 1.2, 4)
    mesh_cfg = EngineConfig(size=32, betas=betas, n_sweeps=3, block_size=8,
                            topology="mesh", mesh_shape=(2, 2))
    m_eng = IsingEngine(mesh_cfg)
    m_state = m_eng.init(key)
    assert "data" in str(m_state.sharding.spec)
    m_res = m_eng.run(m_state, key)

    s_cfg = EngineConfig(size=32, betas=betas, n_sweeps=3, block_size=8)
    s_eng = IsingEngine(s_cfg)
    s_res = s_eng.run(s_eng.init(key), key)
    np.testing.assert_array_equal(np.asarray(m_res.state),
                                  np.asarray(s_res.state))
    print("MESH_ENGINE_OK")
    """, devices=4)
    assert "MESH_ENGINE_OK" in out


_OLD_INIT = {
    # The eager full-lattice construction that mesh init used to run on the
    # default device before device_put; the sharded init must equal it.
    "ising2d": """
        cfg = EngineConfig(size=64, beta=beta, n_sweeps=1, block_size=8,
                           topology="mesh", mesh_shape=(2, 2), hot=hot)
        full = (L.random_lattice(key, 64, 64, jnp.bfloat16) if hot
                else L.cold_lattice(64, 64, jnp.bfloat16))
        q = L.to_quads(full)
        want = jnp.stack([L.block(q[i], 8) for i in range(4)])
    """,
    "ising3d": """
        cfg = EngineConfig(size=8, beta=beta, n_sweeps=1, dims=3,
                           topology="mesh", mesh_shape=(2, 2), hot=hot)
        want = (I3.random_lattice3d(key, 8, 8, 8, jnp.bfloat16) if hot
                else I3.cold_lattice3d(8, 8, 8, jnp.bfloat16))
    """,
    "potts_cb": """
        cfg = EngineConfig(size=16, beta=beta, n_sweeps=1, model="potts",
                           q=3, topology="mesh", mesh_shape=(2, 2), hot=hot)
        want = (potts_state.random_state(key, 16, 16, 3) if hot
                else potts_state.cold_state(16, 16))
    """,
    "potts_cluster": """
        cfg = EngineConfig(size=32, beta=beta, n_sweeps=1, model="potts",
                           q=3, algorithm="swendsen_wang", block_size=8,
                           topology="mesh", mesh_shape=(2, 2), hot=hot)
        full = (potts_state.random_state(key, 32, 32, 3) if hot
                else potts_state.cold_state(32, 32))
        q = L.to_quads(full)
        want = jnp.stack([L.block(q[i], 8) for i in range(4)])
    """,
}


@pytest.mark.parametrize("family", sorted(_OLD_INIT))
def test_sharded_init_matches_full_lattice_construction(subproc, family):
    """Mesh init builds the state already sharded (never the global lattice
    on one device) and stays bitwise equal to the old eager full-lattice
    construction, hot and cold."""
    body = textwrap.indent(textwrap.dedent(_OLD_INIT[family]), " " * 8)
    out = subproc(f"""
    import numpy as np, jax, jax.numpy as jnp
    from repro.api import IsingEngine, EngineConfig
    from repro.core import ising3d as I3
    from repro.core import lattice as L
    from repro.potts import state as potts_state
    key = jax.random.PRNGKey(3)
    for hot, beta in ((True, 0.3), (False, 0.6)):
{body}
        eng = IsingEngine(cfg)
        got = eng.init(key)
        assert got.sharding == eng.state_sharding(), got.sharding
        assert len(got.sharding.device_set) == 4
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    print("SHARDED_INIT_OK")
    """, devices=4)
    assert "SHARDED_INIT_OK" in out
