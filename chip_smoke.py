#!/usr/bin/env python3
"""Smoke test of the engine's main paths on a TPU.

    python chip_smoke.py            # one chip: Table 1, kernels, serving
    python chip_smoke.py --chips 4  # 2x2 mesh only: Table-2 decomposition

One chip runs four phases through the public entry points (``IsingEngine``,
``MCServeEngine``, ``repro.configs``):

  b. the paper's Table-1 lattice ``ising-160x128`` (20480^2 bf16 spins) on
     the XLA paper pipeline: a cold start at beta=0.5 must reach Onsager's
     spontaneous magnetization, a hot start at beta=0.3 must stay disordered;
  c. the same lattice and checks on the integer-threshold opt pipeline;
  d. both Pallas kernels, compiled for the chip (``tpu_custom_call`` in the
     program), bitwise equal to the ``ref`` oracle under both update rules,
     and once more at 20480^2;
  e. a mixed batch of served requests (Ising Metropolis, Swendsen-Wang,
     Wolff, Potts q=3), each bitwise equal to its standalone engine run.

``--chips 4`` runs only the mesh phase: 2-D Ising at 20480^2 per chip
(40960^2 global) on the XLA and ``pallas_lines`` paths against Onsager, and
the sharded 512^3 cube bitwise equal to one chip.

Timings and memory printed on the way are smoke output, not benchmark
metrics. Any failed check exits non-zero before the last line, which is
one JSON object naming the device when every phase passed.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

TABLE1 = "ising-160x128"
SWEEPS = 200
BETA_ORDERED, BETA_DISORDERED = 0.5, 0.3
M_TOL = 0.005     # |m| tolerance; bf16 acceptance shifts |m| by ~1e-3
KERNEL_RULES = ("metropolis", "heat_bath")


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        print(f"[smoke] FAILED: {what}", file=sys.stderr, flush=True)
        raise SystemExit(1)
    log(f"ok: {what}")


def onsager_m(beta: float) -> float:
    """Spontaneous magnetization of the infinite 2-D Ising lattice."""
    return (1.0 - math.sinh(2.0 * beta) ** -4) ** 0.125


def peak_gb(jax) -> str:
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "n/a" if peak is None else f"{peak / 1e9:.3f} GB"


def table1_shape():
    from repro.configs import get_ising_config
    c = get_ising_config(TABLE1)
    return (2 * c.height_blocks * c.block_size,
            2 * c.width_blocks * c.block_size, c.block_size, c.dtype)


def run_timed(jax, eng, state, key):
    """Two engine runs: the first compiles, the second is the timed one."""
    res = eng.run(state, key)
    jax.block_until_ready(res.state)
    t0 = time.perf_counter()
    res = eng.run(res.state, jax.random.fold_in(key, 1))
    jax.block_until_ready(res.state)
    return res, time.perf_counter() - t0


def final_m(eng, res) -> float:
    if eng.state_sharding() is not None:
        return abs(eng.stats(res.state)[0])
    return abs(eng.magnetization(res.state))


def phase_table1(jax, pipeline: str, seed: int) -> None:
    from repro.api import EngineConfig, IsingEngine
    h, w, bs, dtype = table1_shape()
    for beta, want in ((BETA_ORDERED, onsager_m(BETA_ORDERED)),
                       (BETA_DISORDERED, 0.0)):
        eng = IsingEngine(EngineConfig(
            size=h, width=w, beta=beta, n_sweeps=SWEEPS, block_size=bs,
            dtype=dtype, pipeline=pipeline))
        k_init, k_chain = jax.random.split(jax.random.PRNGKey(seed))
        res, secs = run_timed(jax, eng, eng.init(k_init), k_chain)
        m = final_m(eng, res)
        log(f"{TABLE1} {h}x{w} {pipeline} beta={beta}: |m|={m:.6f} "
            f"(want {want:.6f}) moments m_abs={res.moments['m_abs']:.6f} "
            f"E={res.moments['E']:.6f}; smoke output: "
            f"{h * w * SWEEPS / secs / 1e9:.3f} flips/ns over {SWEEPS} "
            f"sweeps, peak {peak_gb(jax)}")
        check(abs(m - want) < M_TOL,
              f"{pipeline} |m| at beta={beta} within {M_TOL} of {want:.4f}")
        del res


def _kernel_runs(jax, size: int, rule: str, n_sweeps: int, seed: int):
    """Final state + moments of ref, pallas and pallas_lines from one seed;
    checks each kernel program holds a Mosaic call and ref's does not."""
    import numpy as np
    from repro.api import EngineConfig, IsingEngine
    out = {}
    for backend in ("ref", "pallas", "pallas_lines"):
        cfg = EngineConfig(size=size, beta=0.44, n_sweeps=n_sweeps,
                           backend=backend, rule=rule, hot=True)
        eng = IsingEngine(cfg)
        k_init, k_chain = jax.random.split(jax.random.PRNGKey(seed))
        state = eng.init(k_init)
        bare = IsingEngine(dataclasses.replace(cfg, measure=False))
        text = jax.jit(lambda s, k: bare.run(s, k).state).lower(
            state, k_chain).compile().as_text()
        check(("tpu_custom_call" in text) == (backend != "ref"),
              f"{backend} {rule} {size}^2 compiled "
              f"{'with' if backend != 'ref' else 'without'} a Mosaic kernel")
        t0 = time.perf_counter()
        res = eng.run(state, k_chain)
        jax.block_until_ready(res.state)
        log(f"{backend} {rule} {size}^2 x{n_sweeps} sweeps in "
            f"{time.perf_counter() - t0:.3f}s incl. compile (smoke output)")
        out[backend] = (res.state, res.moments,
                        np.asarray(res.magnetization))
    return out


def phase_kernels(jax, seed: int) -> None:
    import jax.numpy as jnp
    import numpy as np
    h, _, _, _ = table1_shape()
    cases = [(2048, rule, 4) for rule in KERNEL_RULES]
    cases.append((h, "metropolis", 4))
    for size, rule, n in cases:
        out = _kernel_runs(jax, size, rule, n, seed)
        ref_state, ref_mom, ref_m = out.pop("ref")
        for backend, (state, mom, ms) in out.items():
            check(bool(jnp.array_equal(state, ref_state))
                  and mom == ref_mom and np.array_equal(ms, ref_m),
                  f"{backend} == ref bitwise ({rule}, {size}^2, {n} sweeps)")
        del out, ref_state


def serve_workload():
    from repro.core import observables as obs
    from repro.potts import state as potts_state
    from repro.serve import SimRequest
    bc = 1.0 / obs.critical_temperature()
    pc = potts_state.beta_c(3)
    return [
        SimRequest(L=1024, beta=1.05 * bc, n_sweeps=64, n_samples=4, seed=11),
        SimRequest(L=1024, beta=0.95 * bc, n_sweeps=48, n_samples=2, seed=12),
        SimRequest(L=512, beta=bc, n_sweeps=24, n_samples=2, seed=13,
                   algorithm="swendsen_wang"),
        SimRequest(L=256, beta=bc, n_sweeps=24, n_samples=2, seed=14,
                   algorithm="wolff"),
        SimRequest(L=512, beta=pc, n_sweeps=32, n_samples=2, seed=15,
                   model="potts", q=3, rule="heat_bath"),
        SimRequest(L=256, beta=1.1 * pc, n_sweeps=24, n_samples=2, seed=16,
                   model="potts", q=3, algorithm="swendsen_wang"),
    ]


def phase_serve(jax) -> None:
    import numpy as np
    from repro.api import IsingEngine
    from repro.serve import MCServeEngine
    reqs = serve_workload()
    t0 = time.perf_counter()
    results = MCServeEngine(replica_width=2, chunk_sweeps=16).serve(reqs)
    log(f"served {len(reqs)} requests in {time.perf_counter() - t0:.3f}s "
        f"incl. compile (smoke output)")
    for i, (req, res) in enumerate(zip(reqs, results)):
        ref = IsingEngine(req.engine_config()).simulate(seed=req.seed)
        same = (res.moments == ref.moments
                and np.array_equal(res.magnetization,
                                   np.asarray(ref.magnetization))
                and np.array_equal(res.energy, np.asarray(ref.energy)))
        check(same, f"request {i} ({req.model} {req.algorithm} {req.rule} "
                    f"L={req.L}) == standalone run bitwise")


def phase_mesh(jax, seed: int) -> None:
    import numpy as np
    from repro.api import EngineConfig, IsingEngine
    from repro.core import ising3d as I3
    h, w, bs, dtype = table1_shape()
    want = onsager_m(BETA_ORDERED)
    for backend in ("xla", "pallas_lines"):
        eng = IsingEngine(EngineConfig(
            size=2 * h, width=2 * w, beta=BETA_ORDERED, n_sweeps=SWEEPS,
            block_size=bs, dtype=dtype, backend=backend, topology="mesh",
            mesh_shape=(2, 2)))
        k_init, k_chain = jax.random.split(jax.random.PRNGKey(seed))
        res, secs = run_timed(jax, eng, eng.init(k_init), k_chain)
        m, e = eng.stats(res.state)
        log(f"mesh 2x2 {2 * h}x{2 * w} {backend} beta={BETA_ORDERED}: "
            f"|m|={abs(m):.6f} E={e:.6f} (want |m| {want:.6f}); smoke "
            f"output: {4 * h * w * SWEEPS / secs / 1e9:.3f} flips/ns over "
            f"{SWEEPS} sweeps, device-0 peak {peak_gb(jax)}")
        check(abs(abs(m) - want) < M_TOL,
              f"mesh {backend} |m| within {M_TOL} of Onsager {want:.4f}")
        del res

    side, n = 512, 20
    kw = dict(size=side, beta=I3.BETA_C_3D, n_sweeps=n, dims=3, hot=True,
              measure=False)
    one = IsingEngine(EngineConfig(**kw)).simulate(seed=seed).state
    t0 = time.perf_counter()
    sharded = IsingEngine(EngineConfig(
        topology="mesh", mesh_shape=(2, 2), **kw)).simulate(seed=seed).state
    jax.block_until_ready(sharded)
    log(f"sharded {side}^3 cube x{n} sweeps in "
        f"{time.perf_counter() - t0:.3f}s incl. compile (smoke output)")
    check(np.array_equal(np.asarray(sharded), np.asarray(one)),
          f"sharded {side}^3 cube == one chip bitwise ({n} sweeps)")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 runs only the 2x2 mesh phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    dev = devices[0]
    log(f"jax {jax.__version__}: {len(devices)} x {dev.platform} "
        f"({dev.device_kind})")
    check(dev.platform == "tpu", "a TPU is attached")
    check(len(devices) >= args.chips, f"at least {args.chips} chips")

    from repro import compile_cache
    log(f"compile cache: {compile_cache.enable()}")
    t0 = time.perf_counter()
    if args.chips == 4:
        phase_mesh(jax, args.seed)
    else:
        phase_table1(jax, "paper", args.seed)
        phase_table1(jax, "opt", args.seed)
        phase_kernels(jax, args.seed)
        phase_serve(jax)
    log(f"all phases passed in {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
