"""Production Ising/Potts simulation launcher (the paper's Table 1/2
workload).

A thin CLI over :class:`repro.api.IsingEngine`: mesh topology with spatial
domain decomposition + halo exchange (2-D quads, the 3-D cube, or Potts
colour lattices), periodic exact-stats logging, and checkpointing of the
state (restart-safe long chains for EVERY scenario — the checkpoint
template/sharding come from the engine, so mesh, 3-D, Potts, and
multi-replica ensembles all resume bitwise).

    # paper Table 2 rehearsal on 8 virtual devices:
    PYTHONPATH=src python -m repro.launch.simulate --devices 8 --mesh 2,2,2 \
        --blocks-per-device 2 --block-size 64 --sweeps 200

    # 3-D cube sharded 2x2:
    PYTHONPATH=src python -m repro.launch.simulate --devices 4 --mesh 2,2 \
        --dims 3 --block-size 8 --sweeps 100

    # q=3 Potts heat-bath checkerboard on a mesh:
    PYTHONPATH=src python -m repro.launch.simulate --devices 4 --mesh 2,2 \
        --model potts --q 3 --rule heat_bath --sweeps 100
"""
import argparse
import os
import sys
import time


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=0)
    ap.add_argument("--mesh", default="1,1")
    ap.add_argument("--blocks-per-device", type=int, default=2)
    ap.add_argument("--block-size", type=int, default=128)
    ap.add_argument("--sweeps", type=int, default=100)
    ap.add_argument("--chunk", type=int, default=50,
                    help="sweeps per compiled chunk (checkpoint cadence)")
    ap.add_argument("--temperature-ratio", type=float, default=1.0)
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--dims", type=int, default=2, choices=[2, 3],
                    help="2-D quads or the 3-D cube (side = "
                         "blocks-per-device * block-size, sharded over "
                         "the mesh's trailing axes)")
    ap.add_argument("--pipeline", default="paper", choices=["paper", "opt"])
    ap.add_argument("--rule", default="metropolis",
                    choices=["metropolis", "heat_bath"])
    ap.add_argument("--algo", default="metropolis",
                    choices=["metropolis", "swendsen_wang", "wolff"],
                    help="single-site checkerboard dynamics or the "
                         "cluster-update plane (fast mixing at T_c)")
    ap.add_argument("--model", default="ising", choices=["ising", "potts"],
                    help="spin model; potts requires --q (checkerboard "
                         "AND cluster dynamics both run on a mesh)")
    ap.add_argument("--q", type=int, default=0,
                    help="Potts states (>= 2, with --model potts); "
                         "temperature-ratio is then relative to the exact "
                         "T_c(q) = 1/ln(1+sqrt(q))")
    ap.add_argument("--replicas", type=int, default=0,
                    help="run a vmapped multi-beta ensemble of N replicas "
                         "spanning [temperature-ratio, t-ratio-max] x Tc "
                         "(single-device topology)")
    ap.add_argument("--t-ratio-max", type=float, default=0.0,
                    help="upper T/Tc of the replica ladder "
                         "(default: temperature-ratio + 0.2)")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if args.model == "potts" and args.q < 2:
        ap.error("--model potts requires --q >= 2 (e.g. --q 3)")
    if args.dims == 3 and args.model == "potts":
        ap.error("--dims 3 runs the Ising cube; potts is 2-D")
    if args.dims == 3 and args.replicas:
        ap.error("--replicas ensembles are 2-D (the vmapped replica "
                 "runner sweeps compact quads); drop --dims 3")

    if args.devices:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.devices}")

    import jax
    import jax.numpy as jnp
    from repro import compile_cache
    from repro.api import EngineConfig, IsingEngine
    from repro.checkpoint import ckpt
    from repro.core import observables as obs
    from repro.launch import mesh as mesh_lib

    compile_cache.enable()
    shape = tuple(int(x) for x in args.mesh.split(","))
    axes = ("pod", "data", "model")[3 - len(shape):]
    mesh = mesh_lib.make_mesh(shape, axes)
    nrows = 1
    for a in axes[:-1] or axes[:1]:
        nrows *= mesh.shape[a]
    ncols = mesh.shape[axes[-1]]
    bs = args.block_size

    if args.model == "potts":
        from repro.potts import state as potts_state
        tc = 1.0 / potts_state.beta_c(args.q)
    elif args.dims == 3:
        from repro.core import ising3d as I3
        tc = 1.0 / I3.BETA_C_3D
    else:
        tc = obs.critical_temperature()
    t = args.temperature_ratio * tc

    common = dict(model=args.model, q=args.q, pipeline=args.pipeline,
                  rule=args.rule, algorithm=args.algo, dtype=args.dtype,
                  n_sweeps=args.chunk, measure=False, hot=True)
    if args.replicas:
        h = w = 2 * args.blocks_per_device * bs
        t_max = args.t_ratio_max or (args.temperature_ratio + 0.2)
        # Ladder from the MODEL's Tc (already resolved above): beta here is
        # the engine's native coupling — the q-state Potts coupling for
        # --model potts, where the Ising-Tc ladder would be wildly off.
        n = args.replicas
        step = ((t_max - args.temperature_ratio) / (n - 1) if n > 1
                else 0.0)
        betas = tuple(1.0 / ((args.temperature_ratio + i * step) * tc)
                      for i in range(n))
        engine = IsingEngine(EngineConfig(
            size=h, betas=betas, topology="single", block_size=bs,
            **common))
        spins = args.replicas * h * w
        desc = f"{args.replicas} replicas of {h}x{w}"
    elif args.dims == 3:
        side = args.blocks_per_device * bs
        engine = IsingEngine(EngineConfig(
            size=side, beta=1.0 / t, dims=3, topology="mesh",
            mesh_shape=shape, mesh_axes=axes, **common), mesh=mesh)
        spins = side ** 3
        desc = f"{side}^3 cube"
    else:
        mr = args.blocks_per_device * nrows
        mc = args.blocks_per_device * ncols
        h, w = 2 * mr * bs, 2 * mc * bs
        engine = IsingEngine(EngineConfig(
            size=h, width=w, beta=1.0 / t, topology="mesh",
            mesh_shape=shape, mesh_axes=axes, block_size=bs,
            prob_dtype="bfloat16", **common), mesh=mesh)
        spins = h * w
        desc = f"{h}x{w}"
    print(f"[simulate] mesh={dict(mesh.shape)} lattice {desc} "
          f"({spins/1e6:.1f}M spins) model={args.model}"
          f"{f'(q={args.q})' if args.model == 'potts' else ''} "
          f"dims={args.dims} T/Tc={args.temperature_ratio} "
          f"dtype={args.dtype} algo={args.algo}")

    key = jax.random.PRNGKey(args.seed)
    start_sweep = 0
    if args.ckpt_dir and ckpt.latest_step(args.ckpt_dir) is not None:
        start_sweep = ckpt.latest_step(args.ckpt_dir)
        sh = engine.state_sharding()
        qb = ckpt.restore(args.ckpt_dir, {"qb": engine.state_template()},
                          shardings=({"qb": sh} if sh is not None
                                     else None))["qb"]
        if sh is None:
            qb = jnp.asarray(qb)
        print(f"[simulate] restored lattice at sweep {start_sweep}")
    else:
        qb = engine.init(key)

    mesh_scen = engine._scenario() in engine._MESH_SCENARIOS
    done = start_sweep
    t_total = 0.0
    while done < args.sweeps:
        n = min(args.chunk, args.sweeps - done)
        t0 = time.perf_counter()
        qb = engine.run_sweeps(qb, jax.random.fold_in(key, done), n)
        jax.block_until_ready(qb)
        dt = time.perf_counter() - t0
        t_total += dt
        done += n
        if mesh_scen:
            m, e = engine.stats(qb)  # exact psum stats, no lattice gather
            print(f"[simulate] sweep {done:6d}  m={m:+.4f}  "
                  f"E/spin={e:+.4f}  {n * spins / dt / 1e9:.4f} flips/ns")
        else:
            if args.model == "potts":
                # mean colour index is meaningless; log the replica-mean
                # Potts order parameter instead
                from repro.potts import state as potts_state
                views = qb if qb.ndim == 3 else qb[None]
                m = float(jnp.mean(jax.vmap(
                    lambda f: potts_state.order_parameter(f, args.q))(
                        views)))
            else:
                m = engine.magnetization(qb)
            print(f"[simulate] sweep {done:6d}  m={m:+.4f}  "
                  f"{n * spins / dt / 1e9:.4f} flips/ns")
        if args.ckpt_dir:
            ckpt.save(args.ckpt_dir, {"qb": qb}, step=done, keep=2)
    print(f"[simulate] {args.sweeps - start_sweep} sweeps, "
          f"avg {(args.sweeps - start_sweep) * spins / t_total / 1e9:.4f} "
          f"flips/ns")
    return 0


if __name__ == "__main__":
    sys.exit(main())