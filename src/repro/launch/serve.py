"""Simulation-as-a-service launcher: drive the continuous-batched MC
serving engine with a seeded synthetic workload.

    # 16 mixed ising/potts requests, 8-wide replica buckets:
    PYTHONPATH=src python -m repro.launch.serve --requests 16 \
        --replica-width 8 --chunk 16 --sweeps 200

    # verify one served request bitwise against a standalone engine run:
    PYTHONPATH=src python -m repro.launch.serve --requests 4 --verify

The workload generator draws request shapes, couplings, and seeds from
``--seed`` — rerunning the same command replays the exact same request
stream (and, by the serving plane's batching-independence guarantee, the
exact same per-request results).
"""
from __future__ import annotations

import argparse
import random
import sys
import time


def make_workload(n: int, sizes, models, sweeps: int, samples: int,
                  seed: int) -> list:
    """n seeded pseudo-random requests across the requested shape mix."""
    from repro.serve import SimRequest
    rng = random.Random(seed)
    out = []
    for i in range(n):
        model = rng.choice(models)
        size = rng.choice(sizes)
        kw = dict(L=size, n_sweeps=sweeps, n_samples=samples,
                  seed=rng.randrange(1 << 30))
        if model == "potts":
            q = rng.choice((2, 3))
            from repro.potts import state as potts_state
            kw.update(model="potts", q=q,
                      beta=rng.uniform(0.8, 1.2) * potts_state.beta_c(q),
                      rule=rng.choice(("heat_bath", "metropolis")))
        else:
            from repro.core import observables as obs
            beta_c = 1.0 / obs.critical_temperature()
            algo = rng.choice(("metropolis", "metropolis",
                               "swendsen_wang", "wolff"))
            kw.update(beta=rng.uniform(0.8, 1.2) * beta_c, algorithm=algo)
        out.append(SimRequest(**kw))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="continuous-batched MC serving launcher")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--replica-width", type=int, default=8,
                    help="replica slots per bucket run")
    ap.add_argument("--chunk", type=int, default=16,
                    help="sweeps per compiled chunk (admission cadence)")
    ap.add_argument("--sizes", default="32,64",
                    help="comma-separated lattice sides to mix")
    ap.add_argument("--models", default="ising,potts")
    ap.add_argument("--sweeps", type=int, default=200)
    ap.add_argument("--samples", type=int, default=4,
                    help="streamed snapshots per request")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--verify", action="store_true",
                    help="re-run one request standalone and check the "
                         "served moments are bitwise identical")
    ap.add_argument("--quiet", action="store_true")
    args = ap.parse_args(argv)

    from repro import compile_cache
    from repro.serve import MCServeEngine
    compile_cache.enable()
    sizes = tuple(int(s) for s in args.sizes.split(","))
    models = tuple(args.models.split(","))
    reqs = make_workload(args.requests, sizes, models, args.sweeps,
                         args.samples, args.seed)
    engine = MCServeEngine(replica_width=args.replica_width,
                           chunk_sweeps=args.chunk)

    def on_update(u):
        if not args.quiet:
            mark = "done" if u.done else f"{u.sweeps_done} sweeps"
            print(f"[serve] req {u.request_id:3d} {mark:>12s}  "
                  f"|m|={u.moments['m_abs']:.4f}  E={u.moments['E']:+.4f}")

    t0 = time.perf_counter()
    results = engine.serve(reqs, callback=on_update)
    wall = time.perf_counter() - t0

    lat = sorted(r.latency for r in results)
    p50 = lat[len(lat) // 2]
    p99 = lat[min(len(lat) - 1, int(0.99 * len(lat)))]
    spins = sum(r.n_spins() * r.n_sweeps for r in reqs)
    print(f"[serve] {len(results)} requests in {wall:.2f}s "
          f"({len(results) / wall:.2f} req/s, "
          f"{spins / wall / 1e6:.2f} Msites/s aggregate) "
          f"latency P50={p50:.2f}s P99={p99:.2f}s")

    if args.verify:
        from repro.api import IsingEngine
        req, res = reqs[0], results[0]
        ref = IsingEngine(req.engine_config()).simulate(seed=req.seed)
        same = all(ref.moments[k] == res.moments[k] for k in ref.moments)
        print(f"[serve] bitwise batching-independence check "
              f"(req 0 vs standalone engine): "
              f"{'OK' if same else 'MISMATCH'}")
        if not same:
            return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
