"""``correct`` fails where it should: the control, and faults planted in the
timed path underneath a run whose look for a chip is skipped.

The sizes here are small enough for the CPU; ``bench/tools/control.py``
reads the control at each cell's own size on the chip.
"""

import time
from pathlib import Path

import jax
import numpy as np
import pytest

from harness import control, runner

ROOT = Path(__file__).resolve().parents[2]
SMALL = {"traffic_long": 20_000}


def small_cell(name, root):
    cell = runner.load_cell(name, root=root)
    if cell.traffic["kind"] == "closed_loop":
        cell.traffic["text_bytes"] = SMALL[name]
    else:
        cell.traffic.update(rate_per_s=60, size_bytes=[64, 594], size_weights=[7, 4],
                            checked_requests=40)
    return cell


def execute(cell, seed, wrap=None):
    return runner.execute(cell, seed, 0.6, False, time.perf_counter(),
                          jax.devices()[:1], lambda m: None, wrap_parser=wrap)


# traffic_requests is held out of BENCHMARK.json (bench/held_out.json)
ONE_CHIP = ["traffic_long", "traffic_requests"]


@pytest.mark.parametrize("name", ONE_CHIP)
def test_sound_run_is_correct(name, held_out_root):
    out = execute(small_cell(name, held_out_root), 2**31 + 3)
    assert out["correct"], out["checks"]
    assert all(c["value"] == 0 for c in out["checks"].values())


@pytest.mark.parametrize("name", ONE_CHIP)
def test_control_is_not_correct(name, held_out_root):
    cell = small_cell(name, held_out_root)
    out = execute(cell, 2**31 + 4, control.wrap(cell.config["regex"]))
    assert not out["correct"]
    assert out["checks"]["rows_differ"]["value"] > 0


@pytest.mark.parametrize("name", ONE_CHIP)
def test_answer_altered_where_produced_is_not_correct(name, monkeypatch, held_out_root):
    from repro.core.engine import ParserEngine

    assemble = ParserEngine._assemble

    def altered(self, col0, cols, classes):
        slpf = assemble(self, col0, cols, classes)
        slpf.columns[len(classes) // 2, 0] ^= True      # one segment bit, mid-text
        return slpf

    monkeypatch.setattr(ParserEngine, "_assemble", altered)
    out = execute(small_cell(name, held_out_root), 2**31 + 5)
    assert not out["correct"]
    assert out["checks"]["rows_differ"]["value"] > 0


def test_half_the_batch_left_out_is_not_correct(monkeypatch, held_out_root):
    from repro.serve.parse_service import ParseService

    execute_batch = ParseService._execute

    def half(self, bucket, batch):
        return execute_batch(self, bucket, batch)[: len(batch) // 2]

    cell = small_cell("traffic_requests", held_out_root)
    # the warm-up would stall on the dropped half; the window is what is judged
    monkeypatch.setattr(cell.kind, "_warm", lambda run, parser, texts: None)
    monkeypatch.setattr(ParseService, "_execute", half)
    out = execute(cell, 2**31 + 6)
    assert not out["correct"]
    assert out["checks"]["missing"]["value"] > 0


def test_control_columns_keep_every_reachable_segment():
    from harness.reference import Reference

    ref = Reference("(ab|a)(b|bc)")
    clean = ref.packed_columns(b"abc")
    fwd = ref.packed_columns(b"abc", clean=False)
    assert ((clean & ~fwd) == 0).all() and (fwd != clean).any()
    assert np.array_equal(ref.packed_columns(b"abc", clean=True), clean)
