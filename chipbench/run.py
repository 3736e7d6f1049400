#!/usr/bin/env python3
"""Benchmark harness: one cell, one process, one JSON line.

    python chipbench/run.py --workload t1-20480.metropolis --seed 7 \
        --seconds 20 --trace 0

A cell is ``workloads/<name>.json``: the configuration it runs
(``configs/<config>.json``, the fields of ``repro.api.EngineConfig``, where
they come from, and the ``reference`` that checks them), its traffic
(``traffic/<traffic>.json``), the chips it needs and the limits of its
correctness check. A configuration's ``reference`` names a module in
``references/``; per-layer metrics are the modules in ``metrics/``, found
by listing the directory. Adding a cell, a configuration, its reference, a
traffic mix or a metric is adding files.

A run:

1. Set-up: refuse a configuration that its reference does not model, or
   a limit on a number the reference does not give; refuse to run without
   a TPU holding the cell's chips; turn on the persistent compilation
   cache inside the checkout; build the lattice from ``--seed`` through
   ``IsingEngine.init`` and run chunk 0 (burn-in), which compiles the one
   chunk program the window drives.
2. Window: ``IsingEngine.run`` on chunk i = 1, 2, ... keyed
   ``fold_in(chain_key, i)``, each on the state the last one left, until
   ``--seconds`` have passed; it ends when the last chunk is back.
   ``flips_per_ns`` is global sites x sweeps over the window.
3. Check: the configuration's plain reference
   (``references/<reference>.py``) redoes the window's last chunk from the
   program's own input to it (``check_chunk``) and reads the moments of
   every chunk of the window (``check_window``).
4. ``--trace 1``: the profiler records the first chunks of the window
   inside harness spans, and the line reports the per-layer metrics.

The last line of standard output is the result; the numbers compared, each
with its limit, are the last lines of standard error and the last key of
the result.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent / "src"))

import devtrace  # noqa: E402
import scopes  # noqa: E402
import work  # noqa: E402

TRACE_CHUNKS = 2     # chunks the profiler records in a --trace 1 run
BURN_IN_CHUNK = 0


class NoChip(RuntimeError):
    """The machine lacks the accelerator the cell asks for."""


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    config: dict
    traffic: dict
    chips: int
    limits: dict
    root: Path = HERE


def _read(kind: str, name: str, root: Path) -> dict:
    with open(root / kind / f"{name}.json") as f:
        return json.load(f)


def load_cell(name: str, root: Path = HERE) -> Cell:
    w = _read("workloads", name, root)
    return Cell(name=name, config=_read("configs", w["config"], root),
                traffic=_read("traffic", w["traffic"], root),
                chips=int(w["chips"]), limits=dict(w["limits"]),
                root=root)


def _load(path: Path):
    """The module in ``path``, loaded once per process."""
    name = f"chipbench_{path.parent.name}_{path.stem}"
    mod = sys.modules.get(name)
    if mod is None or Path(mod.__file__) != path:
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        sys.modules[name] = mod
    return mod


def metric_modules(root: Path = HERE) -> dict:
    """Every per-layer metric reader in ``metrics/``, by file name."""
    return {path.stem: _load(path)
            for path in sorted((root / "metrics").glob("*.py"))}


def reference_of(cell: Cell):
    """The reference module that the cell's configuration names, once it
    has accepted the configuration and gives every number the cell holds
    to a limit."""
    name = cell.config["reference"]
    ref = _load(cell.root / "references" / f"{name}.py")
    ref.validate(cell.config)
    missing = sorted(set(cell.limits) - set(ref.NUMBERS))
    if missing:
        raise ValueError(f"workload {cell.name!r} holds {missing} to limits; "
                         f"references/{name} gives only {list(ref.NUMBERS)}")
    return ref


def peaks(kind: str, root: Path = HERE) -> dict:
    with open(root / "peaks.json") as f:
        table = json.load(f)
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


def require_devices(jax, chips: int) -> list:
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devices[0].platform}")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found "
                     f"{len(devices)}")
    return devices


def enable_compile_cache(jax) -> None:
    """JAX's persistent cache in the checkout (or where
    ``JAX_COMPILATION_CACHE_DIR`` says), for every program: a second run
    of a cell compiles nothing."""
    from repro import compile_cache
    compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def make_engine(cell: Cell, overrides: dict):
    import dataclasses as dc

    from repro.api import EngineConfig, IsingEngine

    fields = {f.name for f in dc.fields(EngineConfig)}
    kw = {k: (tuple(v) if isinstance(v, list) else v)
          for k, v in cell.config.items() if k in fields}
    kw.update(overrides)
    return IsingEngine(EngineConfig(n_sweeps=cell.traffic["chunk_sweeps"],
                                    **kw))


def log(msg: str) -> None:
    print(f"[chipbench] {msg}", file=sys.stderr, flush=True)


def _peak_bytes(devices) -> int:
    peaks_ = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
              for d in devices]
    return int(max(peaks_))


def run_cell(cell: Cell, seed: int, seconds: float, traced: bool,
             overrides: dict | None = None, t0: float = T0) -> dict:
    """One run of ``cell``; returns the result object."""
    import jax
    import jax.numpy as jnp

    ref = reference_of(cell)
    devices = require_devices(jax, cell.chips)[:cell.chips]
    enable_compile_cache(jax)

    engine = make_engine(cell, overrides or {})
    n = cell.traffic["chunk_sweeps"]
    k_init, k_chain = jax.random.split(jax.random.PRNGKey(seed))
    state = engine.init(k_init)
    # chunk 0: burn-in, and the warm-up of every program the window runs
    prev = jnp.copy(state)
    res = engine.run(prev, jax.random.fold_in(k_chain, BURN_IN_CHUNK))
    state = jax.block_until_ready(res.state)
    del prev

    log_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if traced else None
    chunk_moments = []
    t_start = time.perf_counter()
    setup_s = t_start - t0
    tracing = traced
    if tracing:
        jax.profiler.start_trace(log_dir)
        window_span = jax.profiler.TraceAnnotation("window")
        window_span.__enter__()
    while True:
        i = len(chunk_moments) + 1
        with jax.profiler.TraceAnnotation("chunk.copy"):
            prev = jnp.copy(state)
        key = jax.random.fold_in(k_chain, i)
        with jax.profiler.TraceAnnotation("chunk.dispatch"):
            res = engine.run(state, key)
        with jax.profiler.TraceAnnotation("chunk.sync"):
            state = jax.block_until_ready(res.state)
            chunk_moments.append(res.moments)
        if tracing and i == TRACE_CHUNKS:
            window_span.__exit__(None, None, None)
            jax.profiler.stop_trace()
            tracing = False
        if time.perf_counter() - t_start >= seconds and not tracing:
            break
    window_s = time.perf_counter() - t_start
    memory_peak = _peak_bytes(devices)
    last_key = key
    del res, engine

    chunks = len(chunk_moments)
    sweeps = chunks * n
    sites = work.sites(cell.config)
    log(f"setup {setup_s:.3f} s, window {window_s:.3f} s, {chunks} chunks "
        f"of {n} sweeps, peak {memory_peak} B")
    t_check = time.perf_counter()
    checks = ref.check_chunk(cell.config, prev, state, last_key, n,
                             chunk_moments[-1])
    log(f"reference check of the last chunk took "
        f"{time.perf_counter() - t_check:.3f} s")
    checks.update(ref.check_window(cell.config, chunk_moments))
    correct = all(checks[k] <= cell.limits[k] for k in cell.limits)

    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": memory_peak}
    out = {"correct": correct, "attempted": sweeps,
           "failed": 0 if correct else sweeps}
    if traced:
        events = devtrace.extract(log_dir)
        shutil.rmtree(log_dir, ignore_errors=True)
        tr = devtrace.Trace(events)
        modules = metric_modules(cell.root)
        ctx = Context(trace=tr, cell=cell, sweeps=TRACE_CHUNKS * n,
                      peaks=peaks(dev.device_kind),
                      scope_names=scopes.declared(modules.values()))
        metrics = {}
        for name, mod in modules.items():
            value = mod.read(ctx)
            if value is not None:
                metrics[name] = {"value": value, "unit": mod.UNIT}
        out["metrics"] = metrics
        device["busy_s"] = tr.mean_busy_ns() / 1e9
        device["window_s"] = tr.window_ns / 1e9
        out["device"] = device
        out["breakdown"] = {"device_ops": tr.top_ops(),
                            "idle_gaps": tr.idle_gaps()}
    else:
        out["metrics"] = {
            "flips_per_ns": {"value": sites * sweeps / (window_s * 1e9),
                             "unit": "flips/ns"},
            "setup_s": {"value": setup_s, "unit": "s"}}
        out["device"] = device
    out["checks"] = {k: {"value": checks[k], "limit": cell.limits[k]}
                     for k in cell.limits}
    return out


@dataclasses.dataclass(frozen=True)
class Context:
    """What a per-layer metric reader gets: the reduced trace of the
    traced chunks, the cell, the sweeps traced, the chip's peaks and the
    scopes that device time is charged to (the program's and those the
    metric readers declare)."""
    trace: devtrace.Trace
    cell: Cell
    sweeps: int
    peaks: dict
    scope_names: tuple = scopes.SCOPES


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR",
                          os.path.join(tempfile.gettempdir(), "tpu_logs"))
    cell = load_cell(args.workload)
    try:
        out = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    except NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    for name, c in out["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
