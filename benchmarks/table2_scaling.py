"""Paper Table 2: weak scaling over cores (virtual devices on CPU).

The paper's claim is *linear weak scaling*: per-core sub-lattice fixed,
flips/ns proportional to core count, wall-time per sweep constant. On CPU
the virtual devices share physical cores, so wall-time scaling is
meaningless — instead we verify the two things the container CAN measure:

  1. the sweep compiles and runs for every mesh size with the per-device
     lattice held fixed (the weak-scaling setup itself),
  2. the collective traffic per device stays CONSTANT as the mesh grows
     (parsed from the compiled HLO) — the structural property that produces
     the paper's linear scaling on real interconnects.

Run in a CPU-only subprocess per mesh size (jax locks the device count per
process, and a child must never ask for a chip its parent holds). A failed
child fails the run.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

from benchmarks.common import emit

_CHILD = """
import os, json
import jax, jax.numpy as jnp
from repro.core import lattice as L
from repro.distributed import ising as dising
from repro.launch import mesh as mesh_lib
from repro.analysis import hlo as H

shape = tuple(json.loads(os.environ["MESH_SHAPE"]))
axes = ("pod", "data", "model")[3 - len(shape):]
mesh = mesh_lib.make_mesh(shape, axes)
row_axes = tuple(a for a in ("pod", "data") if a in mesh.shape) or axes[:1]
cfg = dising.DistIsingConfig(beta=0.4406868, block_size=64,
                             row_axes=row_axes, col_axes=(axes[-1],),
                             prob_dtype="bfloat16")
nrows = 1
for a in row_axes:
    nrows *= mesh.shape[a]
ncols = mesh.shape[axes[-1]]
mr, mc, bs = 2 * nrows, 2 * ncols, 64          # fixed per-device lattice
qb = jax.ShapeDtypeStruct((4, mr, mc, bs, bs), jnp.bfloat16,
                          sharding=dising.lattice_sharding(mesh, cfg))
key = jax.ShapeDtypeStruct((2,), jnp.uint32)
step = jax.ShapeDtypeStruct((), jnp.int32)
sweep = dising.make_sweep_fn(mesh, cfg)
compiled = sweep.lower(qb, key, step).compile()
s = H.collective_summary(compiled.as_text(), mesh.devices.size)
print("RESULT=" + json.dumps({
    "devices": int(mesh.devices.size),
    "wire_bytes_per_device": s["wire_bytes_per_device"],
    "collectives": s["count"],
    "spins": 4 * mr * mc * bs * bs,
}))
"""


def run(meshes=((1, 2), (2, 2), (2, 4), (2, 2, 2), (2, 2, 4))):
    rows, failed = [], []
    for shape in meshes:
        n = 1
        for x in shape:
            n *= x
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
        env["MESH_SHAPE"] = json.dumps(list(shape))
        env["PYTHONPATH"] = "src" + os.pathsep + env.get("PYTHONPATH", "")
        p = subprocess.run([sys.executable, "-c", textwrap.dedent(_CHILD)],
                           capture_output=True, text=True, env=env,
                           timeout=1200)
        if p.returncode != 0:
            emit(f"table2_mesh_{'x'.join(map(str, shape))}", 0.0,
                 f"FAILED: {p.stderr[-200:]}")
            failed.append(shape)
            continue
        line = [l for l in p.stdout.splitlines() if l.startswith("RESULT=")][0]
        r = json.loads(line[len("RESULT="):])
        rows.append(r)
        emit(f"table2_mesh_{'x'.join(map(str, shape))}", 0.0,
             f"devices={r['devices']} "
             f"wire_bytes_per_dev={r['wire_bytes_per_device']:.0f} "
             f"spins_per_dev={r['spins']}")
    # constant per-device traffic == the linear-scaling structural claim.
    # baseline: the first mesh that splits BOTH lattice axes (a 1-D split
    # exchanges halos in one direction only and would skew the ratio).
    both = [r for r in rows if r["devices"] >= 4]
    if len(both) >= 2:
        w0, wN = both[0]["wire_bytes_per_device"], both[-1]["wire_bytes_per_device"]
        ratio = wN / max(w0, 1e-9)
        emit("table2_weak_scaling_wire_ratio", 0.0,
             f"last_over_first={ratio:.3f} (linear scaling iff ~<=1.0)")
    if failed:
        raise RuntimeError(f"table2 children failed for meshes {failed}")
    return rows


def main():
    run()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
