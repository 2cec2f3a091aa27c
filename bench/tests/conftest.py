import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]


import json  # noqa: E402

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def held_out_root(tmp_path_factory):
    """A checkout's root whose BENCHMARK.json also admits the cells of
    ``bench/held_out.json``, for ``runner.load_cell(name, root=...)``."""
    root = tmp_path_factory.mktemp("held_out")
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    held = json.loads((BENCH / "held_out.json").read_text())
    for key in ("workloads", "end_to_end", "per_layer"):
        bench[key] += held.get(key, [])
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    (root / "bench").symlink_to(BENCH)
    return root
