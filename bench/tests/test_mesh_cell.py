"""``traffic_long_mesh4`` resolved by name and run through the harness on four
virtual CPU devices, with a tiny closed-loop mix in place of its 8 MB one."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def test_mesh_cell_runs_on_four_devices(tmp_path):
    """``traffic_long_mesh4``'s configuration through ``runner.execute`` on
    four virtual CPU devices ((2, 2) pod×data): the answers match the
    reference."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (tmp_path / "bench/traffic/long_text_8mb.json").write_text(json.dumps({
        "kind": "closed_loop", "text_bytes": 6000, "checked_parses": 2, "profiled_parses": 1,
        "phase_split_parses": 1,
    }))
    code = f"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path[:0] = [{str(tmp_path / "bench")!r}, {str(ROOT / "src")!r}]
from pathlib import Path
import jax
from harness import runner
assert len(jax.devices()) == 4
cell = runner.load_cell("traffic_long_mesh4", root=Path({str(tmp_path)!r}))
assert cell.chips == 4 and cell.config["parser"]["mesh"] == "host"
out = runner.execute(cell, 2**31 + 11, 1.0, False, 0.0, jax.devices(), lambda m: None)
assert out["correct"] and out["attempted"] >= 2, out
assert set(out["metrics"]) == {{"text_MBps", "setup_s"}}, out
assert out["device"]["count"] == 4
print("MESH4-CELL-OK")
"""
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True,
                       timeout=300, env=env)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "MESH4-CELL-OK" in r.stdout
