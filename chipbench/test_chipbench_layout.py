"""The benchmark's files: layout, names, discovery by name, references,
peaks, work.

Runs on the CPU; nothing here needs a chip.
"""
from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path.insert(0, str(HERE))

import run  # noqa: E402
import scopes  # noqa: E402
import work  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def bench() -> dict:
    with open(ROOT / "BENCHMARK.json") as f:
        return json.load(f)


def names_of(kind: str) -> list:
    return sorted(p.stem for p in (HERE / kind).glob("*.json"))


@pytest.mark.parametrize("cell", names_of("workloads"))
def test_every_workload_names_existing_files(cell):
    w = json.loads((HERE / "workloads" / f"{cell}.json").read_text())
    assert (HERE / "configs" / f"{w['config']}.json").is_file()
    assert (HERE / "traffic" / f"{w['traffic']}.json").is_file()
    assert w["chips"] in (1, 4)
    c = run.load_cell(cell)
    assert c.limits["spins_differ"] == 0
    assert work.chips(c.config) <= c.chips


def test_benchmark_json_matches_the_files():
    b = bench()
    assert b["paths"] == ["chipbench"]
    cells = {w["name"]: w for w in b["workloads"]}
    assert sorted(cells) == names_of("workloads")
    for name, entry in cells.items():
        w = json.loads((HERE / "workloads" / f"{name}.json").read_text())
        for key in ("config", "traffic", "chips", "why"):
            assert entry[key] == w[key], (name, key)
    configs = {c["name"]: c for c in b["configs"]}
    assert sorted(configs) == names_of("configs")
    for name, entry in configs.items():
        assert entry["file"] == f"chipbench/configs/{name}.json"
        cfg = json.loads((ROOT / entry["file"]).read_text())
        assert cfg["name"] == name and cfg["reduced"] == entry["reduced"]
        assert cfg["source"] == entry["source"]
        assert all(k in cfg for k in entry["reduced"])
    assert len({c["source"] for c in configs.values()}) == len(configs)
    metrics = sorted(m["name"] for m in b["per_layer"])
    assert metrics == sorted(run.metric_modules())
    for m in b["per_layer"]:
        assert m["moves"] in {e["name"] for e in b["end_to_end"]}
        assert set(m.get("workloads", cells)) <= set(cells)


def test_names_and_units_are_plain():
    b = bench()
    entries = b["configs"] + b["workloads"] + b["end_to_end"] + b["per_layer"]
    for e in entries:
        assert NAME.match(e["name"]), e["name"]
    for e in b["workloads"]:
        assert NAME.match(e["config"]) and NAME.match(e["traffic"])
        assert 1 <= len(e["why"]) <= 200
    for e in b["configs"]:
        assert all(NAME.match(k) for k in e["reduced"])
    for e in b["end_to_end"] + b["per_layer"]:
        assert UNIT.match(e["unit"]), e["unit"]
        assert e["better"] in ("lower", "higher")
    for mod in run.metric_modules().values():
        assert UNIT.match(mod.UNIT)
    all_names = [e["name"] for e in entries]
    assert len(all_names) == len(set(all_names))


@pytest.mark.parametrize("config", names_of("configs"))
def test_configs_leave_the_path_to_the_program(config):
    cfg = json.loads((HERE / "configs" / f"{config}.json").read_text())
    assert "backend" not in cfg and "pipeline" not in cfg
    assert len(cfg["source"]) <= 200


@pytest.mark.parametrize("config", names_of("configs"))
def test_every_config_names_a_reference_that_models_it(config):
    cfg = json.loads((HERE / "configs" / f"{config}.json").read_text())
    path = HERE / "references" / f"{cfg['reference']}.py"
    assert path.is_file()
    ref = run._load(path)
    ref.validate(cfg)
    assert {"check_chunk", "check_window", "NUMBERS"} <= set(vars(ref))


@pytest.fixture
def harness(monkeypatch):
    """``run_cell`` on the CPU: no look for a chip, no compilation cache;
    ``made`` counts the engines built."""
    made = []
    make = run.make_engine
    monkeypatch.setattr(run, "require_devices",
                        lambda jax, chips: jax.devices())
    monkeypatch.setattr(run, "enable_compile_cache", lambda jax: None)
    monkeypatch.setattr(run, "make_engine",
                        lambda cell, over: made.append(1) or make(cell, over))
    return made


TOY = '''"""Holds the streamed moments to their ranges."""
NUMBERS = ("m_abs_over_1", "no_chunks")


def validate(config):
    if config.get("dims", 2) != 2:
        raise ValueError("references/toy does not model dims")


def check_chunk(config, start, final, key, n_sweeps, moments):
    return {"m_abs_over_1": max(0.0, float(moments["m_abs"]) - 1.0)}


def check_window(config, chunk_moments):
    return {"no_chunks": int(not chunk_moments)}
'''


def test_new_files_are_found_without_an_edit(tmp_path, harness):
    """A configuration of other dynamics brings its reference, its
    scope-share metric and its cell as files, and runs."""
    root = tmp_path / "chipbench"
    shutil.copytree(HERE, root,
                    ignore=shutil.ignore_patterns("__pycache__", "testdata"))
    cfg = json.loads((root / "configs" / "ising2d-table1-20480.json")
                     .read_text())
    cfg.update(name="ising2d-small", size=64, block_size=16,
               reference="toy")
    (root / "configs" / "ising2d-small.json").write_text(json.dumps(cfg))
    (root / "references" / "toy.py").write_text(TOY)
    (root / "traffic" / "short.json").write_text(
        json.dumps({"chunk_sweeps": 10}))
    (root / "workloads" / "small.short.json").write_text(json.dumps(
        {"config": "ising2d-small", "traffic": "short", "chips": 1,
         "why": "a new cell",
         "limits": {"m_abs_over_1": 0, "no_chunks": 0}}))
    (root / "metrics" / "label_share.py").write_text(
        'import scopes\n\nUNIT = "%"\nSCOPE = "label"\n\n\n'
        'def read(ctx):\n    return scopes.share(ctx, SCOPE)\n')
    cell = run.load_cell("small.short", root)
    assert cell.config["size"] == 64
    assert cell.traffic["chunk_sweeps"] == 10
    assert "label_share" in run.metric_modules(root)
    assert set(run.metric_modules()) < set(run.metric_modules(root))
    assert scopes.declared(run.metric_modules(root).values()) == \
        scopes.SCOPES + ("label",)
    out = run.run_cell(cell, seed=2**31 + 29, seconds=0.2, traced=False)
    assert out["correct"] is True, out["checks"]
    assert set(out["checks"]) == {"m_abs_over_1", "no_chunks"}
    assert out["metrics"]["flips_per_ns"]["value"] > 0
    assert harness == [1]


def test_reference_refuses_a_config_it_does_not_model(harness):
    """A Swendsen-Wang configuration paired with the Metropolis reference
    fails at set-up, naming the module and the key, before any engine."""
    cell = run.load_cell("t1-20480.metropolis")
    cell = run.Cell(name="sw.short", root=cell.root, chips=1,
                    config={**cell.config, "size": 64, "block_size": 16,
                            "algorithm": "swendsen_wang"},
                    traffic={"chunk_sweeps": 4}, limits=cell.limits)
    with pytest.raises(ValueError,
                       match=r"metropolis2d does not model algorithm="):
        run.run_cell(cell, seed=3, seconds=0.2, traced=False)
    assert harness == []


@pytest.mark.parametrize("key,value", [
    ("model", "potts"), ("dims", 3), ("algorithm", "wolff"),
    ("rule", "heat_bath"), ("accept", "exp"), ("pipeline", "opt"),
    ("ensemble", "tempering"), ("betas", [0.4, 0.5]), ("field", 0.1),
    ("measure", False), ("measure_every", 2)])
def test_metropolis2d_refuses_what_it_does_not_redo(key, value):
    cfg = json.loads((HERE / "configs" / "ising2d-table1-20480.json")
                     .read_text())
    ref = run._load(HERE / "references" / "metropolis2d.py")
    ref.validate(cfg)
    with pytest.raises(ValueError, match=rf"metropolis2d .* {key}="):
        ref.validate({**cfg, key: value})


def test_a_limit_the_reference_does_not_give_is_refused(harness):
    cell = run.load_cell("t1-20480.metropolis")
    cell = run.Cell(name=cell.name, root=cell.root, chips=1,
                    config={**cell.config, "size": 64, "block_size": 16},
                    traffic={"chunk_sweeps": 4},
                    limits={**cell.limits, "energy_gap": 1e-3})
    with pytest.raises(ValueError, match=r"\['energy_gap'\].*metropolis2d"):
        run.run_cell(cell, seed=3, seconds=0.2, traced=False)
    assert harness == []


def test_unknown_device_kind_is_an_error():
    assert run.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peaks"):
        run.peaks("TPU v9 imaginary")


@pytest.mark.parametrize("config", ["ising2d-table1-20480",
                                    "ising2d-table2-weak-2x2"])
def test_work_is_every_spin_read_and_written_once(config):
    cfg = json.loads((HERE / "configs" / f"{config}.json").read_text())
    assert work.sweep_bytes_per_chip(cfg) == 2 * 20480 ** 2 * 2
    for extra in ({"backend": "pallas_lines"}, {"pipeline": "opt"}):
        assert work.sweep_bytes_per_chip({**cfg, **extra}) == \
            work.sweep_bytes_per_chip(cfg)


def test_work_counts_every_site_of_a_cube():
    cube = {"size": 1024, "dims": 3, "dtype": "bfloat16"}
    assert work.sites(cube) == 1024 ** 3
    assert work.sweep_bytes_per_chip(cube) == 2 * 1024 ** 3 * 2
    assert work.sites({"size": 64, "dims": 2}) == 64 * 64
    assert work.sites({"size": 64, "width": 32}) == 64 * 32


def _run_py(cwd: Path, env_extra: dict) -> subprocess.CompletedProcess:
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(env_extra)
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "t1-20480.metropolis", "--seed", "3", "--seconds", "1",
         "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_json_line(stdout: str) -> bool:
    return not any(line.lstrip().startswith("{")
                   for line in stdout.splitlines())


def test_run_refuses_the_cpu():
    proc = _run_py(ROOT, {"JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    assert _no_json_line(proc.stdout)
    assert "no TPU" in proc.stderr


def test_run_fails_without_the_program(tmp_path):
    """A checkout of the benchmark alone: past the chip check (skipped
    here), the run stops where it looks for the program."""
    shutil.copytree(HERE, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    code = ("import sys; sys.path.insert(0, 'chipbench'); import run; "
            "run.require_devices = lambda jax, chips: jax.devices(); "
            "sys.exit(run.main(['--workload', 't1-20480.metropolis', "
            "'--seed', '3', '--seconds', '1']))")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, "-c", code], cwd=tmp_path,
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert _no_json_line(proc.stdout)
    assert "No module named 'repro'" in proc.stderr
