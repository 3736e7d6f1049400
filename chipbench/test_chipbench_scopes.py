"""Device time by the program's named scopes (``scopes.py``).

The map from compiled instruction to scope is checked on a hand-written HLO
module whose answers are worked out below, the share arithmetic on a
hand-built trace. ``testdata/<cell>.scoped.json.gz`` holds, for each cell,
one ``--trace 1`` run on the chip as ``tools/record_trace.py`` kept it: what
:func:`devtrace.extract` returned, the chunk program's map from op to scope,
the program's ``engine.*`` spans and the line the run printed. Reduced here,
it must give the printed numbers.
"""
from __future__ import annotations

import gzip
import json
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import devtrace  # noqa: E402
import run  # noqa: E402
import scopes  # noqa: E402
import work  # noqa: E402

SHARES = {"rng_share": "rng", "layout_share": "layout",
          "halo_lines_share": "halo", "measure_share": "measure"}

HLO = '''HloModule jit_run, entry_computation_layout={(bf16[8]{0})->bf16[8]{0}}

%fused_computation.1 (param_0.1: u32[8]) -> bf16[8] {
  %param_0.1 = u32[8]{0} parameter(0)
  %xor.1 = u32[8]{0} xor(u32[8]{0} %param_0.1, u32[8]{0} %param_0.1), metadata={op_name="jit(run)/while/body/sweep/rng/jit(_uniform)/xor"}
  %max.1 = f32[8]{0} maximum(f32[8]{0} %xor.1, f32[8]{0} %xor.1), metadata={op_name="jit(run)/while/body/sweep/rng/jit(_uniform)/max"}
  %constant.1 = f32[] constant(0), metadata={op_name="jit(run)/while/body"}
  ROOT %convert.1 = bf16[8]{0:T(8,128)(2,1)} convert(f32[8]{0} %max.1)
}

%fused_computation.2 (param_0.2: bf16[8], param_1.2: bf16[8]) -> (f32[], bf16[8]) {
  %param_0.2 = bf16[8]{0} parameter(0)
  %param_1.2 = bf16[8]{0} parameter(1)
  %compare.1 = pred[8]{0} compare(%param_0.2, %param_1.2), direction=LT, metadata={op_name="jit(run)/while/body/sweep/flip/rng/lt"}
  %select.1 = bf16[8]{0} select(%compare.1, %param_0.2, %param_1.2), metadata={op_name="jit(run)/while/body/sweep/flip/select_n"}
  %negate.1 = bf16[8]{0} negate(%param_0.2), metadata={op_name="jit(run)/while/body/sweep/flip/neg"}
  %reduce.1 = f32[] reduce(%select.1), metadata={op_name="jit(run)/while/body/sweep/measure/reduce_sum"}
  ROOT %tuple.1 = (f32[], bf16[8]{0}) tuple(f32[] %reduce.1, bf16[8]{0} %select.1)
}

%fused_computation.3 (param_0.3: bf16[8]) -> f32[] {
  %param_0.3 = bf16[8]{0} parameter(0)
  %add.3 = bf16[8]{0} add(%param_0.3, %param_0.3), metadata={op_name="jit(run)/while/body/sweep/rng/add"}
  %mul.3 = bf16[8]{0} multiply(%add.3, %add.3), metadata={op_name="jit(run)/while/body/sweep/rng/mul"}
  ROOT %reduce.3 = f32[] reduce(%mul.3), metadata={op_name="jit(run)/while/body/sweep/measure/reduce_sum"}
}

ENTRY %main.9 (Arg_0.1: bf16[8]) -> bf16[8] {
  %Arg_0.1 = bf16[8]{0} parameter(0), metadata={op_name="quads"}
  %maximum_convert_fusion.2 = bf16[8]{0:T(8,128)(2,1)} fusion(u32[8]{0} %Arg_0.1), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(run)/convert.4"}
  %compare_select_fusion = (f32[], bf16[8]{0}) fusion(%maximum_convert_fusion.2, %Arg_0.1), kind=kLoop, calls=%fused_computation.2
  %reduce_fusion = f32[] fusion(%Arg_0.1), kind=kInput, calls=%fused_computation.3, backend_config={"a":"b"}
  %copy.3 = bf16[8]{0} copy(bf16[8]{0} %maximum_convert_fusion.2)
  %copy.4 = bf16[8]{0} copy(bf16[8]{0} %Arg_0.1), metadata={op_name="jit(run)/shard_map/reshape.1138"}
  %dynamic-update-slice.5 = bf16[8]{0} dynamic-update-slice(%copy.3, %Arg_0.1), metadata={op_name="jit(run)/while/body/sweep/halo/scatter-add"}
  %bitcast.7 = bf16[8]{0} bitcast(bf16[8]{0} %dynamic-update-slice.5)
  %add.9 = bf16[8]{0} add(%bitcast.7, %bitcast.7), metadata={op_name="jit(run)/while/body/closed_call"}
  %reshape.1 = bf16[8]{0} reshape(%add.9), metadata={op_name="jit(run)/sweep/layout/broadcast_in_dim;sweep/rng/reshape"}
  ROOT %negate.9 = bf16[8]{0} negate(%reshape.1)
}
'''


def test_scope_names_are_the_programs():
    from repro.core import lattice
    assert scopes.SCOPES == lattice.SCOPES


def test_innermost_scope_of_an_op_name():
    assert scopes.innermost("jit(f)/while/body/sweep/rng/jit(_uniform)/xor") \
        == "rng"
    assert scopes.innermost("jit(f)/sweep/flip/rng/convert_element_type") \
        == "rng"
    assert scopes.innermost("jit(f)/shard_map/while/body") == ""
    assert scopes.innermost("a/halo/add;sweep/layout/reshape") == "halo"


def test_scope_map_of_a_compiled_module():
    got = scopes.scope_map(HLO)
    want = {
        # root without metadata, own metadata XLA's: most of its ops
        "maximum_convert_fusion.2": "rng",
        # a tuple root: most of its ops (flip 2, rng 1, measure 1)
        "compare_select_fusion": "flip",
        # a root with a scope: the root's, though most ops say rng
        "reduce_fusion": "measure",
        "copy.3": "layout",                     # a copy XLA added
        "copy.4": "layout",                     # one the partitioner named
        "dynamic-update-slice.5": "halo",
        "bitcast.7": "halo",                    # from its operand
        "add.9": "",                            # metadata with no scope
        "reshape.1": "layout",                  # the first of joined paths
        "negate.9": "layout",
        "Arg_0.1": "",
        "convert.1": "rng",
        "tuple.1": "measure",
    }
    assert {k: got[k] for k in want} == want


def test_a_declared_scope_is_charged_like_the_programs():
    """A ``label`` scope that only a metric reader declares: the module
    with ``flip`` renamed ``label`` maps as before, with ``label``."""
    reader = type(sys)("label_share")
    reader.SCOPE = "label"
    names = scopes.declared([reader, scopes])
    assert names == scopes.SCOPES + ("label",)
    assert scopes.innermost("jit(f)/sweep/label/while/body/min", names) \
        == "label"
    assert scopes.innermost("jit(f)/sweep/label/while/body/min") == "sweep"
    want = {k: "label" if v == "flip" else v
            for k, v in scopes.scope_map(HLO).items()}
    assert scopes.scope_map(HLO.replace("/flip/", "/label/"), names) == want


def op(name: str, opcode: str) -> str:
    return f"%{name} = bf16[8]{{0}} {opcode}(bf16[8]{{0}} %p.1)"


def test_shares_by_scope():
    """Window 0-100. TPU:0: rng 0-30, halo 30-50, an unknown op 50-60,
    and an op half outside the window (90-110): busy 70. TPU:1: rng 0-50,
    busy 50."""
    events = {
        "host": [["window", 0, 100]],
        "devices": {
            "TPU:0": [[op("fusion.1", "fusion"), 0, 30],
                      [op("copy.2", "copy"), 30, 20],
                      [op("other.3", "add"), 50, 10],
                      [op("fusion.4", "fusion"), 90, 20]],
            "TPU:1": [[op("fusion.1", "fusion"), 0, 50]],
        },
    }
    scope_of = {"fusion.1": "rng", "copy.2": "halo", "fusion.4": "flip"}
    got = scopes.shares(devtrace.Trace(events), scope_of)
    assert got["rng"] == pytest.approx((100 * 30 / 70 + 100) / 2)
    assert got["halo"] == pytest.approx(100 * 20 / 70 / 2)
    assert got[""] == pytest.approx(100 * 10 / 70 / 2)
    assert got["flip"] == pytest.approx(100 * 10 / 70 / 2)
    assert sum(got.values()) == pytest.approx(100)
    assert got["layout"] == 0


RECORDED = sorted(HERE.glob("testdata/*.events.json.gz"))


@pytest.mark.parametrize("path", RECORDED, ids=lambda p: p.name)
def test_share_metrics_are_silent_off_the_chip(path):
    cell = run.load_cell(path.name[:-len(".events.json.gz")])
    ctx = run.Context(trace=devtrace.Trace(devtrace.load(str(path))),
                      cell=cell, sweeps=100,
                      peaks=run.peaks("TPU v5 lite"))
    metrics = run.metric_modules()
    assert all(metrics[name].read(ctx) is None for name in SHARES)


SCOPED = sorted(HERE.glob("testdata/*.scoped.json.gz"))


def _recorded(path: Path):
    with gzip.open(path, "rt") as f:
        rec = json.load(f)
    cell = run.load_cell(path.name[:-len(".scoped.json.gz")])
    return rec, cell, devtrace.Trace(rec)


@pytest.mark.parametrize("path", SCOPED, ids=lambda p: p.name)
def test_recorded_run_reduces_to_what_it_printed(path):
    rec, cell, tr = _recorded(path)
    printed = {k: v["value"] for k, v in rec["printed"]["metrics"].items()}
    got = scopes.shares(tr, rec["scopes"])
    assert {name: got[scope] for name, scope in SHARES.items()} == \
        pytest.approx({name: printed[name] for name in SHARES}, rel=1e-12)
    ctx = run.Context(trace=tr, cell=cell, sweeps=rec["sweeps_traced"],
                      peaks=run.peaks(rec["printed"]["device"]["kind"]))
    for name, mod in run.metric_modules().items():
        if name not in SHARES:
            assert mod.read(ctx) == pytest.approx(printed.get(name),
                                                  rel=1e-12), name
    assert sum(got.values()) == pytest.approx(100, abs=0.05)


@pytest.mark.parametrize("path", SCOPED, ids=lambda p: p.name)
def test_recorded_shares_hold_with_a_declared_scope(path):
    rec, _, tr = _recorded(path)
    before = scopes.shares(tr, rec["scopes"])
    after = scopes.shares(tr, rec["scopes"], scopes.SCOPES + ("label",))
    assert after == {**before, "label": 0.0}
    assert before["layout"] > 0 and before["halo"] > 0


@pytest.mark.parametrize("path", SCOPED, ids=lambda p: p.name)
def test_recorded_random_draw_is_charged_to_rng(path):
    rec, _, _ = _recorded(path)
    draws = {n: s for n, s in rec["scopes"].items()
             if n.startswith("maximum_convert_fusion")}
    assert draws and set(draws.values()) == {"rng"}


@pytest.mark.parametrize("path", SCOPED, ids=lambda p: p.name)
def test_recorded_engine_spans_cover_the_traced_work(path):
    rec, cell, _ = _recorded(path)
    dispatch = [(s, s + d) for n, s, d in rec["host"]
                if n == "chunk.dispatch"]
    assert len(dispatch) == run.TRACE_CHUNKS
    for name, s, d, _ in rec["engine"]:
        assert name.startswith("engine.")
        assert any(a <= s and s + d <= b for a, b in dispatch), name
    runs = [args for name, _, _, args in rec["engine"]
            if name == "engine.run"]
    assert sum(a["sweeps"] * a["sites"] for a in runs) == \
        run.TRACE_CHUNKS * cell.traffic["chunk_sweeps"] \
        * work.sites(cell.config)
