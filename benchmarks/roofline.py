"""Roofline table (§g of the deliverables): reads the dry-run JSONL written
by ``python -m repro.launch.dryrun --out results/dryrun.jsonl`` and prints
the per-(arch x shape x mesh) three-term roofline. If no JSONL exists, runs
a reduced-mesh subset in a subprocess so `-m benchmarks.run` is self-contained.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

from benchmarks.common import emit

DRYRUN_OUT = os.path.join("results", "dryrun.jsonl")


def load_records(path=DRYRUN_OUT):
    if not os.path.exists(path):
        return []
    recs = []
    with open(path) as f:
        for line in f:
            try:
                recs.append(json.loads(line))
            except json.JSONDecodeError:
                continue
    # newest record per cell wins
    by_key = {}
    for r in recs:
        by_key[(r.get("arch"), r.get("shape"), r.get("mesh"))] = r
    return list(by_key.values())


def print_table(recs):
    print("# arch,shape,mesh,dominant,compute_s,memory_s,collective_s,"
          "useful_ratio,mfu,peak_gb")
    for r in sorted(recs, key=lambda r: (r.get("mesh", ""), r.get("arch", ""),
                                         r.get("shape", ""))):
        if r.get("skipped"):
            emit(f"roofline_{r['arch']}_{r['shape']}_{r['mesh']}", 0.0,
                 "SKIP:" + r.get("reason", "")[:60])
            continue
        if not r.get("ok") or "roofline" not in r:
            emit(f"roofline_{r['arch']}_{r['shape']}_{r['mesh']}", 0.0,
                 "FAIL:" + str(r.get("error"))[:80])
            continue
        rl = r["roofline"]
        peak = r.get("memory", {}).get("peak_gb", 0.0)
        emit(f"roofline_{r['arch']}_{r['shape']}_{r['mesh']}",
             rl["compute_s"] * 0 + max(rl["compute_s"], rl["memory_s"],
                                       rl["collective_s"]),
             f"dominant={rl['dominant']} compute={rl['compute_s']:.3f} "
             f"memory={rl['memory_s']:.3f} coll={rl['collective_s']:.3f} "
             f"useful={rl['useful_flop_ratio']:.3f} mfu={rl['mfu']:.4f} "
             f"peak_gb={peak:.1f}")


_FALLBACK = """
import json
from repro.launch import mesh as mesh_lib
from repro.launch import dryrun_lib as lib
mesh = mesh_lib.make_mesh((2, 4), ("data", "model"))
cells = [("qwen3-0.6b", "train_4k"), ("qwen3-0.6b", "decode_32k"),
         ("mamba2-780m", "long_500k"), ("ising-20x128", "sweep")]
for arch, shape in cells:
    rec = lib.run_cell(arch, shape, mesh, "fallback-2x4", 2)
    print("REC=" + json.dumps(rec))
"""


def run_fallback():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"   # never the chip a parent may hold
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = "src" + os.pathsep + env.get("PYTHONPATH", "")
    p = subprocess.run([sys.executable, "-c", textwrap.dedent(_FALLBACK)],
                       capture_output=True, text=True, env=env, timeout=3600)
    recs = []
    for line in p.stdout.splitlines():
        if line.startswith("REC="):
            recs.append(json.loads(line[len("REC="):]))
    if p.returncode != 0:
        print(f"# fallback dry-run stderr: {p.stderr[-300:]}", file=sys.stderr)
    return recs


def main():
    recs = load_records()
    src = DRYRUN_OUT
    if not recs:
        src = "reduced-mesh fallback (run repro.launch.dryrun for the full table)"
        recs = run_fallback()
    print(f"# roofline source: {src}")
    print_table(recs)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
