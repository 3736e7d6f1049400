"""Benchmark aggregator: one section per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run                 # all sections
    PYTHONPATH=src python -m benchmarks.run fig4            # one section
    PYTHONPATH=src python -m benchmarks.run fig4 --smoke    # CI-sized run
    PYTHONPATH=src python -m benchmarks.run --json          # + BENCH_*.json

Emits ``name,us_per_call,derived`` CSV rows (benchmarks.common.emit). With
``--json`` each section additionally writes machine-readable
``BENCH_<section>.json`` (``{"section", "smoke", "took_s", "rows": [...]}``)
so CI can track the perf trajectory across PRs. ``--smoke`` shrinks each
section to CI scale (tiny lattices, few sweeps) — correctness gates stay on.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

from benchmarks import common

SECTIONS = ("fig4", "cluster", "potts", "mesh3d", "serve", "table1",
            "table2", "kernel", "roofline")


def _run_section(name: str, smoke: bool) -> int:
    if name == "fig4":
        from benchmarks import fig4_correctness
        return fig4_correctness.main(smoke=smoke)
    if name == "cluster":
        from benchmarks import cluster_sweep
        return cluster_sweep.main(smoke=smoke)
    if name == "potts":
        from benchmarks import potts_equiv
        return potts_equiv.main(smoke=smoke)
    if name == "mesh3d":
        from benchmarks import mesh3d
        return mesh3d.main(smoke=smoke)
    if name == "serve":
        from benchmarks import serve_load
        return serve_load.main(smoke=smoke)
    if name == "table1":
        from benchmarks import table1_single_core
        table1_single_core.run(**({"sizes_blocks": (2, 4), "block_size": 32,
                                   "n_sweeps": 2} if smoke else {}))
        return 0
    if name == "table2":
        from benchmarks import table2_scaling
        table2_scaling.run()
        return 0
    if name == "kernel":
        from benchmarks import kernel_micro
        kernel_micro.run(**({"size": 128, "bs": 32} if smoke else {}))
        return 0
    if name == "roofline":
        from benchmarks import roofline
        return roofline.main()
    raise ValueError(name)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("sections", nargs="*",
                    help=f"sections to run (default: all of {SECTIONS})")
    ap.add_argument("--json", action="store_true",
                    help="write BENCH_<section>.json with us_per_call rows")
    ap.add_argument("--json-dir", default=".",
                    help="directory for BENCH_*.json (default: cwd)")
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized parameters (tiny lattice, few sweeps)")
    args = ap.parse_args(argv)
    unknown = set(args.sections) - set(SECTIONS)
    if unknown:
        ap.error(f"unknown sections {sorted(unknown)}; choose from "
                 f"{SECTIONS}")
    wanted = set(args.sections) or set(SECTIONS)
    from repro import compile_cache
    compile_cache.enable()

    rc = 0
    for name in SECTIONS:
        if name not in wanted:
            continue
        print(f"\n### {name} " + "#" * (60 - len(name)))
        rows: list = []
        common.collect_rows(rows)
        t0 = time.time()
        try:
            rc |= _run_section(name, args.smoke)
        except Exception as e:  # noqa: BLE001 — report and continue
            print(f"# section {name} FAILED: {type(e).__name__}: {e}")
            rc = 1
        finally:
            common.collect_rows(None)
        took = time.time() - t0
        print(f"# section {name} took {took:.1f}s")
        if args.json:
            Path(args.json_dir).mkdir(parents=True, exist_ok=True)
            out = Path(args.json_dir) / f"BENCH_{name}.json"
            out.write_text(json.dumps(
                {"section": name, "smoke": args.smoke,
                 "took_s": round(took, 1), "rows": rows}, indent=2) + "\n")
            print(f"# wrote {out}")
    return rc


if __name__ == "__main__":
    raise SystemExit(main())
