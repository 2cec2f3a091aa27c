"""BENCHMARK.json against its schema and limits, and every cell resolved by name."""

import json
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from harness import runner

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]
HELD = json.loads((ROOT / "bench" / "held_out.json").read_text())
HELD_CELLS = [w["name"] for w in HELD["workloads"]]


def test_top_level_keys_and_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "bench/run.py"]
    assert BENCH["paths"] == ["bench"]
    assert isinstance(BENCH["run_seconds"], int) and 1 <= BENCH["run_seconds"] <= 51
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024
    # a full check of 24 cells fits its 43200 s
    rs = BENCH["run_seconds"]
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_entries_have_exactly_the_schema_keys():
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert (ROOT / c["file"]).is_file() and c["file"].startswith("bench/")
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    names = [x["name"] for k in ("configs", "workloads", "end_to_end", "per_layer") for x in BENCH[k]]
    assert all(NAME.match(n) for n in names)
    assert len(set(x["name"] for x in BENCH["end_to_end"] + BENCH["per_layer"])) == len(
        BENCH["end_to_end"] + BENCH["per_layer"])
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= max(1, len(CELLS) // 2)


@pytest.mark.parametrize("cell", CELLS + HELD_CELLS)
def test_cell_resolves_to_its_files(cell, held_out_root):
    c = runner.load_cell(cell, root=ROOT if cell in CELLS else held_out_root)
    w = next(w for w in BENCH["workloads"] + HELD["workloads"] if w["name"] == cell)
    conf = next(x for x in BENCH["configs"] if x["name"] == w["config"])
    assert c.config["name"] == w["config"] and c.config["reduced"] == conf["reduced"]
    assert hasattr(c.kind, "run")
    e2e = {m["name"] for m in c.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert c.per_layer, "every cell reports a per-layer metric"
    for m in c.per_layer:
        assert m["moves"] in e2e
        reader = runner.load_module(ROOT / "bench" / "metrics" / f"{m['name']}.py")
        assert reader.read({}) is None          # nothing to read: no number


def test_every_config_is_used_and_every_metric_has_a_cell():
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}
    for m in BENCH["per_layer"]:
        assert set(m["workloads"]) <= set(CELLS)
    for m in HELD["per_layer"] + HELD["end_to_end"]:
        assert set(m["workloads"]) <= set(HELD_CELLS)


def test_held_out_cells_are_entries_ready_to_admit():
    """Held-out entries keep BENCHMARK.json's schema (less a bound, which is
    set when a cell is admitted) and name nothing BENCHMARK.json has."""
    assert set(HELD) == {"why", "workloads", "end_to_end", "per_layer"}
    for w in HELD["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"} and len(w["why"]) <= 200
        assert w["config"] in {c["name"] for c in BENCH["configs"]}
    for m in HELD["end_to_end"]:
        assert set(m) == {"name", "unit", "better", "source", "workloads"}
    for m in HELD["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
    names = {x["name"] for k in ("workloads", "end_to_end", "per_layer") for x in HELD[k]}
    assert not names & {x["name"] for k in ("workloads", "end_to_end", "per_layer") for x in BENCH[k]}
    with pytest.raises(runner.CellError):
        runner.load_cell(HELD_CELLS[0])          # the benchmark's own runs refuse it


def test_run_refuses_a_cpu_platform():
    env = {"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"}
    r = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "traffic_long", "--seed", "3",
         "--seconds", "1", "--trace", "0"],
        cwd=ROOT, capture_output=True, text=True, timeout=300, env=env,
    )
    assert r.returncode != 0 and r.stdout == ""
    assert "no TPU" in r.stderr


def test_run_refuses_a_checkout_without_the_parser(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "traffic_long", "--seed", "3",
         "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=300,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"},
    )
    assert r.returncode != 0 and r.stdout == ""


def test_a_cell_added_as_files_and_entries_runs_without_edits(tmp_path):
    """A new configuration, traffic mix and cell: data files plus entries."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    (tmp_path / "bench/configs/bigdata_small.json").write_text(json.dumps({
        "name": "bigdata_small", "regex": "(ab|ba|b)+",
        "parser": {"backend": "jnp", "n_chunks": 4}, "reduced": [],
        "text": {"record": [{"one_of": ["ab", "ba", "b"]}]},
    }))
    (tmp_path / "bench/traffic/short_closed.json").write_text(json.dumps({
        "kind": "closed_loop", "text_bytes": 300, "checked_parses": 2, "profiled_parses": 1,
        "phase_split_parses": 1,
    }))
    bench["configs"].append({"name": "bigdata_small", "source": "https://arxiv.org/abs/2503.06763",
                             "file": "bench/configs/bigdata_small.json", "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "bigdata_short", "config": "bigdata_small",
                               "traffic": "short_closed", "chips": 1, "why": "x"})
    bench["end_to_end"][0]["workloads"].append("bigdata_short")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    import jax

    cell = runner.load_cell("bigdata_short", root=tmp_path)
    out = runner.execute(cell, 5, 0.5, False, 0.0, jax.devices()[:1], lambda m: None)
    assert out["correct"] and out["attempted"] >= 1
    assert set(out["metrics"]) == {"text_MBps", "setup_s"}
