"""The names a profile of the sweep carries: device scopes and host spans.

The chunk programs of the one-chip chain and of the 2x2 mesh chain hold ops
under each of ``lattice.SCOPES`` (``jax.named_scope``), both in their
lowered text and after compilation, where a trace reader maps ops to scopes;
``IsingEngine.run`` writes ``engine.run`` with its ``engine.launch`` /
``engine.wait`` / ``engine.moments`` children into the profiler's trace.
Results do not depend on either: the bitwise tests of the sweeps run with
the names in place.
"""
from __future__ import annotations

import glob
import inspect
import json
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.api import EngineConfig, IsingEngine
from repro.api.engine import EngineConfigError
from repro.core import lattice as L

from conftest import run_in_subprocess

SMALL = dict(size=64, beta=0.5, n_sweeps=3, block_size=16, hot=False)
CHILDREN = ("engine.launch", "engine.wait", "engine.moments")

def innermost(op_name):
    for part in reversed(op_name.split(";")[0].split("/")):
        if part in L.SCOPES:
            return part
    return ""


def scopes_in(engine, state):
    """The scopes in the lowered and in the compiled chunk program."""
    low = engine.lower(state, jax.ShapeDtypeStruct((2,), jnp.uint32))
    lowered = {innermost(n) for n in re.findall(
        r'loc\("([^"]*)"', low.as_text(debug_info=True))}
    compiled = {innermost(n) for n in re.findall(
        r'op_name="([^"]*)"', low.compile().as_text())}
    return sorted(lowered - {""}), sorted(compiled - {""})


def engine_spans(engine, state, key, log_dir):
    """[name, start, end, args] of the engine.* spans of a profiled run
    (after one run that compiles)."""
    from jax.profiler import ProfileData
    state = jax.block_until_ready(engine.run(state, key).state)
    with jax.profiler.trace(log_dir):
        jax.block_until_ready(engine.run(state, key).state)
    spans = []
    for path in glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                       "*.xplane.pb")):
        for plane in ProfileData.from_file(path).planes:
            for line in plane.lines:
                spans += [[e.name, e.start_ns, e.end_ns, dict(e.stats)]
                          for e in line.events
                          if e.name.startswith("engine.")]
    return spans


def check_spans(spans, scenario, sweeps, sites):
    runs = [s for s in spans if s[0] == "engine.run"]
    assert len(runs) == 1, spans
    _, start, end, args = runs[0]
    assert args == {"scenario": scenario, "sweeps": sweeps, "sites": sites}
    children = [s for s in spans if s[0] != "engine.run"]
    assert [s[0] for s in sorted(children, key=lambda s: s[1])] == \
        list(CHILDREN)
    for _, s, e, _ in children:
        assert start <= s <= e <= end


def test_chain_program_holds_every_scope():
    eng = IsingEngine(EngineConfig(**SMALL))
    lowered, compiled = scopes_in(eng, eng.state_template())
    assert lowered == compiled == sorted(L.SCOPES)


def test_chain_run_writes_engine_spans(tmp_path):
    eng = IsingEngine(EngineConfig(**SMALL))
    state = eng.init(jax.random.PRNGKey(0))
    spans = engine_spans(eng, state, jax.random.PRNGKey(1), str(tmp_path))
    check_spans(spans, "chain", 3, 64 * 64)


def test_mesh_program_and_spans_on_four_devices(tmp_path):
    helpers = "".join(inspect.getsource(f)
                      for f in (innermost, scopes_in, engine_spans))
    out = run_in_subprocess(f'''
import glob, json, os, re
import jax, jax.numpy as jnp
from repro.api import EngineConfig, IsingEngine
from repro.core import lattice as L
{helpers}
eng = IsingEngine(EngineConfig(size=64, beta=0.5, n_sweeps=3, block_size=16,
                               hot=False, topology="mesh", mesh_shape=(2, 2)))
tmpl = eng.state_template()
state = jax.ShapeDtypeStruct(tmpl.shape, tmpl.dtype,
                             sharding=eng.state_sharding())
lowered, compiled = scopes_in(eng, state)
spans = engine_spans(eng, eng.init(jax.random.PRNGKey(0)),
                     jax.random.PRNGKey(1), {str(tmp_path)!r})
print(json.dumps({{"lowered": lowered, "compiled": compiled,
                  "spans": spans}}))
''', devices=4)
    got = json.loads(out.strip().splitlines()[-1])
    assert got["lowered"] == got["compiled"] == sorted(L.SCOPES)
    check_spans(got["spans"], "mesh", 3, 64 * 64)


def test_lower_covers_the_chain_and_mesh_scenarios_only():
    eng = IsingEngine(EngineConfig(**SMALL, backend="ref"))
    with pytest.raises(EngineConfigError, match="chain and mesh"):
        eng.lower(eng.state_template(),
                  jax.ShapeDtypeStruct((2,), jnp.uint32))
