"""The correctness check: a sound run passes, the control and each fault a
cell can have fail.

The harness runs here on the CPU at a small lattice, with its look for a
chip skipped and the compilation cache left off; everything else is the
run the chip makes: the engine's chunk program, the copy, the reference
over the last chunk and the comparison. The faults are planted under the
timed path: a chunk that returns its state unchanged, one spin altered
where the chunk produces it, and (on a mesh of four CPU devices) the halo
exchange between devices left out. The control is the program's own lower
precision path, ``prob_dtype="bfloat16"``.

A 64^2 lattice is far from Onsager's infinite lattice, so the small cells
hold the exact numbers only (``spins_differ``, ``moments_gap``) at the
committed limits; ``onsager_gap`` is held on the chip.
"""
from __future__ import annotations

import dataclasses
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import run  # noqa: E402

SMALL = {"size": 64, "block_size": 16}
SMALL_MESH = {"size": 128, "block_size": 16, "mesh_shape": [2, 2]}


def small_cell(name: str, **size) -> run.Cell:
    cell = run.load_cell(name)
    limits = {k: v for k, v in cell.limits.items() if k != "onsager_gap"}
    return dataclasses.replace(cell, config={**cell.config, **size},
                               traffic={**cell.traffic, "chunk_sweeps": 4},
                               chips=1, limits=limits)


class Broken:
    """An engine whose chunks come back with one fault planted."""

    def __init__(self, engine, fault: str):
        self.engine, self.fault = engine, fault

    def init(self, key):
        return self.engine.init(key)

    def run(self, state, key):
        import jax.numpy as jnp
        before = jnp.copy(state)
        res = self.engine.run(state, key)
        if self.fault == "unchanged":
            return dataclasses.replace(res, state=before)
        corner = (0,) * res.state.ndim
        return dataclasses.replace(
            res, state=res.state.at[corner].multiply(-1))


@pytest.fixture
def harness(monkeypatch):
    monkeypatch.setattr(run, "require_devices",
                        lambda jax, chips: jax.devices())
    monkeypatch.setattr(run, "enable_compile_cache", lambda jax: None)
    return monkeypatch


def checks(out) -> dict:
    return {k: v["value"] for k, v in out["checks"].items()}


def test_sound_run_is_correct(harness):
    out = run.run_cell(small_cell("t1-20480.metropolis", **SMALL),
                       seed=2**31 + 17, seconds=0.2, traced=False)
    assert out["correct"] is True, out["checks"]
    assert checks(out)["spins_differ"] == 0
    assert out["failed"] == 0 and out["attempted"] % 4 == 0
    assert list(out)[-1] == "checks"


def test_control_is_not_correct(harness):
    out = run.run_cell(small_cell("t1-20480.metropolis", **SMALL), seed=5,
                       seconds=0.2, traced=False,
                       overrides={"prob_dtype": "bfloat16"})
    assert out["correct"] is False
    assert checks(out)["spins_differ"] > 0


@pytest.mark.parametrize("fault", ["unchanged", "altered"])
def test_fault_is_not_correct(harness, fault):
    make = run.make_engine
    harness.setattr(run, "make_engine",
                    lambda cell, over: Broken(make(cell, over), fault))
    out = run.run_cell(small_cell("t1-20480.metropolis", **SMALL), seed=9,
                       seconds=0.2, traced=False)
    assert out["correct"] is False
    assert out["failed"] == out["attempted"]
    want = 1 if fault == "altered" else None
    differ = checks(out)["spins_differ"]
    assert differ == want if want else differ > 0


MESH_SCRIPT = """
import dataclasses, json, sys
sys.path.insert(0, {here!r})
import run, test_chipbench_check as t
run.require_devices = lambda jax, chips: jax.devices()
run.enable_compile_cache = lambda jax: None
cell = t.small_cell("t2-2x2-20480.metropolis", **t.SMALL_MESH)
results = {{}}
def go(name, **kw):
    out = run.run_cell(cell, seed=2**31 + 3, seconds=0.2, traced=False,
                       **kw)
    results[name] = [out["correct"], out["checks"]["spins_differ"]["value"]]
go("sound")
go("control", overrides={{"prob_dtype": "bfloat16"}})
make = run.make_engine
for fault in ("unchanged", "altered"):
    run.make_engine = lambda c, o, f=fault: t.Broken(make(c, o), f)
    go(fault)
run.make_engine = make
from repro.core import checkerboard as cb
from repro.distributed import halo
halo.blocked_quad_edges = lambda spec: cb.default_edges
go("no_exchange")
print(json.dumps(results))
"""


def test_mesh_sound_run_passes_and_each_fault_fails():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = str(HERE.parent / "src")
    proc = subprocess.run(
        [sys.executable, "-c",
         textwrap.dedent(MESH_SCRIPT.format(here=str(HERE)))],
        env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    res = json.loads(proc.stdout.strip().splitlines()[-1])
    assert res["sound"] == [True, 0]
    for name in ("control", "unchanged", "altered", "no_exchange"):
        correct, differ = res[name]
        assert correct is False and differ > 0, (name, res[name])
    assert res["altered"][1] == 1
