#!/usr/bin/env python3
"""Record one ``--trace 1`` run of a cell for the reducer's tests.

    python chipbench/tools/record_trace.py --workload t1-20480.metropolis \
        --seed 2147484001 --seconds 10 --out t1.scoped.json.gz

Runs the cell as ``run.py --trace 1`` does, in one process on the chip, and
keeps beside what :func:`devtrace.extract` returned (the devices' ops and
the harness's spans) what the harness drops: the program's ``engine.*``
host spans with their arguments and the map from op to scope of the chunk
program (``scopes.py``, only the ops the trace holds). It writes them, with
the line the run printed, as gzipped JSON, and prints a summary: device
time by scope, the share of busy time whose ops the map does not know, the
durations of the ``engine.*`` spans and the device idle time inside them
for each ``engine.run``.
"""
from __future__ import annotations

import argparse
import glob
import gzip
import json
import os
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import devtrace  # noqa: E402
import run  # noqa: E402
import scopes  # noqa: E402

ENGINE = "engine."


def engine_spans(log_dir: str) -> list:
    """The ``engine.*`` host spans as [name, start_ns, dur_ns, args]."""
    from jax.profiler import ProfileData

    spans = []
    for path in glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                       "*.xplane.pb")):
        for plane in ProfileData.from_file(path).planes:
            if plane.name.startswith("/host:"):
                for line in plane.lines:
                    spans.extend([e.name, e.start_ns, e.duration_ns,
                                  dict(e.stats)]
                                 for e in line.events
                                 if e.name.startswith(ENGINE))
    return sorted(spans, key=lambda s: s[1])


def engine_idle_ns(trace, spans: list) -> list:
    """Device idle ns inside the ``engine.*`` spans, per ``engine.run``
    span and device: the window's idle intervals met by those spans."""
    runs = [(s, s + d) for n, s, d, _ in spans if n == ENGINE + "run"]
    out = []
    for dev in trace.devices():
        idle = devtrace.subtract([[trace.start, trace.end]], trace.busy[dev])
        inside = devtrace.intersect(
            idle, devtrace.union([s, s + d] for _, s, d, _ in spans))
        out.append([devtrace.length(devtrace.intersect(inside, [[s, e]]))
                    for s, e in runs])
    return out


def summary(rec: dict) -> dict:
    tr = devtrace.Trace(rec)
    scope_of = rec["scopes"]
    known = sum(e - s for dev in tr.devices()
                for name, _, s, e in tr.ops[dev] if name in scope_of)
    total = sum(e - s for dev in tr.devices() for *_, s, e in tr.ops[dev])
    idle = engine_idle_ns(tr, rec["engine"])
    spans: dict = {}
    for n, _, d, _ in rec["engine"]:
        spans.setdefault(n, []).append(d / 1e6)
    declared = set(scope_of.values()) - set(scopes.SCOPES) - {scopes.UNSCOPED}
    names = scopes.SCOPES + tuple(sorted(declared))
    return {"shares": scopes.shares(tr, scope_of, names),
            "unknown_op_share": 100.0 * (1 - known / total) if total else None,
            "engine_idle_ms": [[ns / 1e6 for ns in dev] for dev in idle],
            "engine_span_ms": spans}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR",
                          os.path.join(tempfile.gettempdir(), "tpu_logs"))
    cell = run.load_cell(args.workload)
    extra: dict = {}
    extract = devtrace.extract

    def keep(log_dir):
        events = extract(log_dir)
        extra.update(events, engine=engine_spans(log_dir))
        return events

    devtrace.extract = keep
    out = run.run_cell(cell, args.seed, args.seconds, traced=True,
                       t0=time.perf_counter())
    scope_of = scopes.program_scopes(
        cell, scopes.declared(run.metric_modules(cell.root).values())) or {}
    names = {devtrace.short_name(n) for ops in extra["devices"].values()
             for n, _, _ in ops}
    rec = dict(extra, scopes={n: scope_of[n] for n in sorted(names)
                              if n in scope_of},
               printed=out, seed=args.seed,
               sweeps_traced=run.TRACE_CHUNKS * cell.traffic["chunk_sweeps"])
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    with gzip.open(args.out, "wt") as f:
        json.dump(rec, f)
    print(json.dumps(summary(rec)), file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
