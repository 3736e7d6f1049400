"""The trace reducer and the per-layer metric readers.

Interval arithmetic is checked on a hand-built event list whose answers are
worked out below. ``testdata/`` holds, for each cell, what
:func:`devtrace.extract` returned in one ``--trace 1`` run on the chip
(gzipped JSON) and the result line that run printed; their reduction here
must give the printed numbers.
"""
from __future__ import annotations

import dataclasses
import json
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import devtrace  # noqa: E402
import run  # noqa: E402

V5E = {"hbm_bytes_per_s": 819e9}


def test_opcode_and_name_come_from_the_instruction_text():
    text = ("%while.18 = (s32[]{:T(128)}, bf16[4,10240,10240]{2,1,0:T(8,128)"
            "(2,1)}, f32[50]{0:T(128)}) while((s32[]{:T(128)}) %tuple.103), "
            "condition=%region_13.26, body=%region_0.25")
    assert devtrace.opcode(text) == "while"
    assert devtrace.short_name(text) == "while.18"
    text = ("%collective-permute-start.3 = (bf16[1,128]{1,0:T(8,128)(2,1)}, "
            "u32[]{:S(2)}) collective-permute-start(bf16[1,128]{1,0} "
            "%slice.7), source_target_pairs={{0,1},{1,0}}")
    assert devtrace.opcode(text) == "collective-permute-start"


def test_union_intersect_subtract():
    assert devtrace.union([[5, 7], [0, 2], [1, 3], [7, 8]]) == [[0, 3], [5, 8]]
    a = [[0, 10], [20, 30]]
    assert devtrace.intersect(a, [[5, 25]]) == [[5, 10], [20, 25]]
    assert devtrace.subtract(a, [[2, 4], [8, 22]]) == \
        [[0, 2], [4, 8], [22, 30]]
    assert devtrace.subtract([[0, 10]], []) == [[0, 10]]
    assert devtrace.length([[0, 3], [5, 8]]) == 6


def op(name: str, opcode: str) -> str:
    """An event name as the XLA Ops line writes it."""
    return (f"%{name} = (bf16[4,8]{{1,0:T(8,128)(2,1)}}, f32[]) "
            f"{opcode}(bf16[4,8]{{1,0}} %p.1), calls=%fused_computation.2")


def synthetic() -> dict:
    """Window 0-100 ns on two devices.

    TPU:0 busy 10-40 (fusion) and 50-80 (fusion 50-60, then a
    collective-permute 55-80 that overlaps it 55-60): busy 60, collective
    25, exposed 20. TPU:1 busy 0-100 minus 30-70: busy 60, all-reduce
    90-100 under a fusion 85-100: collective 10, exposed 0. An op outside
    the window (120-130) is clipped away, and the ``while`` that encloses
    TPU:0's ops is a container, not work.
    """
    return {
        "host": [["window", 0, 100], ["chunk.dispatch", 0, 45],
                 ["chunk.sync", 45, 55]],
        "devices": {
            "TPU:0": [[op("fusion.1", "fusion"), 10, 30],
                      [op("fusion.2", "fusion"), 50, 10],
                      [op("collective-permute-done.4",
                          "collective-permute-done"), 55, 25],
                      [op("fusion.1", "fusion"), 120, 10],
                      [op("while.9", "while"), 0, 100]],
            "TPU:1": [[op("fusion.1", "fusion"), 0, 30],
                      [op("fusion.2", "fusion"), 70, 30],
                      [op("all-reduce.3", "all-reduce"), 90, 10]],
        },
    }


def ctx(events, cell=None, sweeps=1, peaks=V5E):
    return run.Context(trace=devtrace.Trace(events), cell=cell,
                       sweeps=sweeps, peaks=peaks)


def test_busy_idle_and_gaps():
    tr = devtrace.Trace(synthetic())
    assert tr.window_ns == 100
    assert tr.busy_ns("TPU:0") == 60 and tr.busy_ns("TPU:1") == 60
    metrics = run.metric_modules()
    c = ctx(synthetic())
    assert metrics["device_idle_share"].read(c) == pytest.approx(40.0)
    assert metrics["halo_collective_share"].read(c) == pytest.approx(17.5)
    assert metrics["halo_exposed_share"].read(c) == pytest.approx(10.0)
    gaps = tr.idle_gaps()
    assert gaps[0] == ["chunk.sync", pytest.approx(40e-9)]  # TPU:1 30-70
    assert sorted(g[1] for g in gaps) == pytest.approx(
        [10e-9, 10e-9, 20e-9, 40e-9])
    ops = dict(tr.top_ops())
    assert ops["fusion.1"] == pytest.approx(30e-9)   # (30 + 30) / 2 devices


def test_halo_metrics_are_silent_without_collectives():
    ev = synthetic()
    ev["devices"] = {"TPU:0": [[op("fusion.1", "fusion"), 10, 30]]}
    metrics = run.metric_modules()
    assert metrics["halo_collective_share"].read(ctx(ev)) is None
    assert metrics["halo_exposed_share"].read(ctx(ev)) is None
    assert metrics["device_idle_share"].read(ctx(ev)) == pytest.approx(70.0)


def test_roofline_counts_the_configuration_work():
    cell = run.load_cell("t1-20480.metropolis")
    ev = {"host": [["window", 0, 10**9]],
          "devices": {"TPU:0": [[op("fusion", "fusion"), 0, 10**9]]}}
    share = run.metric_modules()["sweep_roofline"].read(
        ctx(ev, cell=cell, sweeps=100))
    # 100 sweeps x 2 x 20480^2 x 2 B over 819 GB/s, in one busy second
    assert share == pytest.approx(100 * 100 * 2 * 20480 ** 2 * 2 / 819e9)
    ev["devices"] = {}
    assert run.metric_modules()["sweep_roofline"].read(
        ctx(ev, cell=cell)) is None


def test_roofline_is_silent_for_a_cluster_sweep():
    cell = run.load_cell("t1-20480.metropolis")
    cell = dataclasses.replace(
        cell, config={**cell.config, "algorithm": "swendsen_wang"})
    ev = {"host": [["window", 0, 10**9]],
          "devices": {"TPU:0": [[op("fusion", "fusion"), 0, 10**9]]}}
    assert run.metric_modules()["sweep_roofline"].read(
        ctx(ev, cell=cell, sweeps=100)) is None


RECORDED = sorted(HERE.glob("testdata/*.events.json.gz"))


@pytest.mark.parametrize("path", RECORDED, ids=lambda p: p.name)
def test_recorded_chip_trace_reduces_to_what_the_run_printed(path):
    cell_name = path.name[:-len(".events.json.gz")]
    printed = json.loads(path.with_name(f"{cell_name}.printed.json")
                         .read_text())
    events = devtrace.load(str(path))
    tr = devtrace.Trace(events)
    c = run.Context(trace=tr, cell=run.load_cell(cell_name),
                    sweeps=printed["sweeps_traced"],
                    peaks=run.peaks(printed["device"]["kind"]))
    got = {name: mod.read(c) for name, mod in run.metric_modules().items()}
    want = {k: v["value"] for k, v in printed["metrics"].items()}
    assert {k: v for k, v in got.items() if v is not None} == \
        pytest.approx(want, rel=1e-12)
    assert tr.mean_busy_ns() / 1e9 == pytest.approx(
        printed["device"]["busy_s"], rel=1e-12)
    assert tr.window_ns / 1e9 == pytest.approx(
        printed["device"]["window_s"], rel=1e-12)
    assert 0 < tr.mean_busy_ns() <= tr.window_ns
    assert len(tr.devices()) == printed["device"]["count"]
