"""3-D domain decomposition: sharded cube Msites/s vs single device.

The paper's any-dimension remark at scale: this section times
``run_sweeps3d`` on one device against the same cube sharded over a
2x2 device grid (``repro.distributed.ising3d``), reporting Msites/s per
sweep for each, plus a correctness gate — the sharded chain must be
BITWISE identical to the single-device chain (the counter-based-RNG
contract the plane is built on).

The sharded half runs in this process when it sees at least four devices.
Otherwise it runs in a child that is CPU-only (``JAX_PLATFORMS=cpu``, four
virtual devices): a chip belongs to the process that touched JAX first, so
the child never asks for it. The child re-emits its rows through this
process's sink.
"""
from __future__ import annotations

import os
import subprocess
import sys
import time
from pathlib import Path

from benchmarks.common import emit

_CHILD = """
from benchmarks.mesh3d import sharded_row
print("ROW," + ",".join(map(str, sharded_row({side}, {n_sweeps}, {beta!r},
                                              {seed}))))
"""


def sharded_row(side: int, n_sweeps: int, beta: float, seed: int) -> tuple:
    """Time the cube sharded over a 2x2 device grid; fails unless the chain
    is bitwise equal to the single-device one. Returns an ``emit`` row."""
    import jax
    from repro.core import ising3d as I3
    from repro.distributed import ising3d as d3
    from repro.launch import mesh as mesh_lib

    key = jax.random.PRNGKey(seed)
    full = I3.random_lattice3d(jax.random.PRNGKey(seed + 1),
                               side, side, side)
    mesh = mesh_lib.make_mesh((2, 2), ("data", "model"))
    cfg = d3.Dist3DConfig(beta=beta, row_axes=("data",),
                          col_axes=("model",))
    run = d3.make_run_sweeps_fn(mesh, cfg, n_sweeps)
    sh = d3.lattice_sharding(mesh, cfg)

    jax.block_until_ready(run(jax.device_put(full, sh), key))  # compile
    t0 = time.perf_counter()
    out = jax.block_until_ready(run(jax.device_put(full, sh), key))
    secs = time.perf_counter() - t0

    want, _ = I3.run_sweeps3d(full, key, n_sweeps, beta)
    if not bool((jax.device_get(out) == jax.device_get(want)).all()):
        raise AssertionError("sharded 3-D chain diverged from single device")
    msites = side ** 3 * n_sweeps / secs / 1e6
    return (f"mesh3d_sharded_2x2_{side}", secs / n_sweeps,
            f"Msites_per_s={msites:.2f} bitwise_eq_single=True")


def run(side=32, n_sweeps=20, smoke=False, seed=0):
    import jax
    from repro.core import ising3d as I3

    if smoke:
        side, n_sweeps = 8, 5
    beta = I3.BETA_C_3D
    print(f"# mesh3d: side={side} sweeps={n_sweeps} beta={beta:.6f} "
          f"smoke={smoke}")

    # -- single device -----------------------------------------------------
    key = jax.random.PRNGKey(seed)
    full = I3.random_lattice3d(jax.random.PRNGKey(seed + 1),
                               side, side, side)
    runner = jax.jit(lambda f, k: I3.run_sweeps3d(f, k, n_sweeps, beta)[0])
    jax.block_until_ready(runner(full, key))    # compile warmup
    t0 = time.perf_counter()
    jax.block_until_ready(runner(full, key))
    secs = time.perf_counter() - t0
    emit(f"mesh3d_single_{side}", secs / n_sweeps,
         f"Msites_per_s={side ** 3 * n_sweeps / secs / 1e6:.2f}")

    # -- sharded 2x2 -------------------------------------------------------
    if len(jax.devices()) >= 4:
        emit(*sharded_row(side, n_sweeps, beta, seed))
        return 0
    repo = Path(__file__).resolve().parent.parent
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [str(repo / "src"), str(repo), env.get("PYTHONPATH", "")])
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    code = _CHILD.format(side=side, n_sweeps=n_sweeps, beta=beta, seed=seed)
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=900)
    if proc.returncode != 0:
        print(proc.stdout)
        print(proc.stderr, file=sys.stderr)
        raise RuntimeError("mesh3d sharded subprocess failed")
    for line in proc.stdout.splitlines():
        if line.startswith("ROW,"):
            _, name, secs_per_sweep, derived = line.split(",", 3)
            emit(name, float(secs_per_sweep), derived)
    return 0


def main(smoke=False) -> int:
    return run(smoke=smoke)


if __name__ == "__main__":
    raise SystemExit(main("--smoke" in sys.argv[1:]))
