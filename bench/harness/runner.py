"""One run of one cell: the command line, set-up, window, check, result line.

``BENCHMARK.json`` names a cell's configuration and traffic mix; the files
are found by name:

    bench/configs/<config>.json   the deployment: pattern, parser settings,
                                  text template, what was assumed and cut
    bench/traffic/<traffic>.json  the mix: its ``kind`` and parameters
    bench/kinds/<kind>.py         the general runner of that kind of traffic
    bench/metrics/<metric>.py     a per-layer metric's reader

The kind's runner (``kinds/*.py``) builds the system under test, warms every shape
its traffic uses, opens the window through ``Run.window_open`` (which fixes
``setup_s``), and returns an ``Outcome``.  This module then compares the
answers the window produced with the plain reference and prints the result
as the last line of standard output.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import importlib.util
import json
import math
import sys
import time
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


class CellError(RuntimeError):
    """BENCHMARK.json or a file it names is missing or malformed."""


def load_module(path: Path):
    """Import a kind runner or a reader from its file (names may hold dots)."""
    if not path.is_file():
        raise CellError(f"{path.relative_to(ROOT)} is missing")
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _read_json(path: Path) -> dict:
    if not path.is_file():
        raise CellError(f"{path.relative_to(ROOT)} is missing")
    return json.loads(path.read_text())


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    kind: Any                      # the kind runner's module
    end_to_end: List[dict]         # this cell's end-to-end metrics
    per_layer: List[dict]          # this cell's per-layer metrics


def _reports(metric: dict, cell: str) -> bool:
    return cell in metric["workloads"] if "workloads" in metric else True


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """Resolve one cell of ``BENCHMARK.json`` to its files."""
    bench = _read_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise CellError(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    config = _read_json(root / "bench" / "configs" / f"{w['config']}.json")
    traffic = _read_json(root / "bench" / "traffic" / f"{w['traffic']}.json")
    kind = load_module(root / "bench" / "kinds" / f"{traffic['kind']}.py")
    e2e = [m for m in bench["end_to_end"] if _reports(m, name)]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [
        m for m in bench["per_layer"]
        if (name in m["workloads"] if "workloads" in m else m["moves"] in e2e_names)
    ]
    return Cell(name, int(w["chips"]), config, traffic, kind, e2e, per_layer)


@dataclasses.dataclass
class Answer:
    """One answer of the window, kept for the comparison with the reference."""

    text: bytes
    columns: Any                   # (n + 1, ℓ) bool, as the program returned it
    accepted: bool


@dataclasses.dataclass
class Outcome:
    """What a kind runner hands back: counts, end-to-end values, per-layer inputs."""

    attempted: int
    failed: int                    # answers due that never came
    end_to_end: Dict[str, float]
    answers: List[Answer]
    memory_peak_bytes: int
    layer_data: Dict[str, Any] = dataclasses.field(default_factory=dict)


class Run:
    """A run's settings and clocks, handed to the kind runner."""

    def __init__(self, cell: Cell, seed: int, seconds: float, trace: bool,
                 t_start: float, devices, clock, log: Callable[[str], None],
                 wrap_parser: Optional[Callable] = None):
        self.cell = cell
        self.seed = seed
        self.seconds = seconds
        self.trace = trace
        self.t_start = t_start
        self.devices = devices
        self.clock = clock
        self.log = log
        self.wrap_parser = wrap_parser
        self.setup_s: Optional[float] = None
        self._clock_at_open: Optional[dict] = None
        self._watch = None
        self._marks: List[tuple] = [("start", t_start)]

    def mark(self, label: str) -> None:
        """End of one part of set-up (reported when the window opens)."""
        self._marks.append((label, time.perf_counter()))

    @property
    def config(self) -> dict:
        return self.cell.config

    @property
    def params(self) -> dict:
        return self.cell.traffic

    def rng(self, stream: int):
        from .textgen import rng_of

        return rng_of(self.seed, stream)

    def template(self):
        from .textgen import Template

        return Template(self.config["text"])

    def build_parser(self, *, traced: bool = False):
        """The system under test, as the configuration states it."""
        from repro import Parser, ParserConfig
        from repro.obs import ObsConfig

        kw = dict(self.config["parser"])
        if traced:
            kw["obs"] = ObsConfig(enabled=True, max_spans=1 << 20, hlo=False)
        parser = Parser(ParserConfig(regex=self.config["regex"], **kw))
        want = self.config.get("expect", {})
        if "backend" in want and parser.backend_name != want["backend"]:
            raise CellError(
                f"{self.config['name']}: backend resolved to "
                f"{parser.backend_name}, the configuration states {want['backend']}"
            )
        if "segments" in want and parser.engine.tables.ell != want["segments"]:
            raise CellError(
                f"{self.config['name']}: {parser.engine.tables.ell} segments, "
                f"the configuration states {want['segments']}"
            )
        return self.wrap_parser(parser) if self.wrap_parser else parser

    def window_open(self) -> float:
        """Mark the end of set-up; returns the window's start on the host clock.

        Set-up's objects (texts, schedules) are moved out of the garbage
        collector's reach first, so that its pauses in the window scan only
        what the window itself allocates.  The window's pauses are watched
        (``harness/stalls.py``) and reported when it closes."""
        from .stalls import StallWatch

        gc.collect()
        gc.freeze()
        now = time.perf_counter()
        self.setup_s = now - self.t_start
        self._clock_at_open = self.clock.snapshot()
        parts = ", ".join(
            f"{label} {t - t_prev:.3f} s"
            for (_, t_prev), (label, t) in zip(self._marks, self._marks[1:] + [("rest", now)])
        )
        self.log(
            f"set-up {self.setup_s:.3f} s = {parts}; compile trace+lower "
            f"{self._clock_at_open['trace_s']:.3f} s, backend "
            f"{self._clock_at_open['backend_s']:.3f} s, "
            f"{self._clock_at_open['compiles']} programs, "
            f"{self._clock_at_open['cache_hits']} persistent-cache hits"
        )
        self._watch = StallWatch().start()
        return now

    def window_closed(self) -> int:
        """Programs compiled or fetched since the window opened (should be 0)."""
        self.log(f"window: {self._watch.stop()}")
        gc.unfreeze()
        n = self.clock.compiles - self._clock_at_open["compiles"]
        self.log(f"compiles inside the window: {n}")
        return n


def compare(answers: List[Answer], pattern: str, log) -> Dict[str, int]:
    """Every kept answer against the plain reference, column by column."""
    from .reference import Reference, pack_columns

    t0 = time.perf_counter()
    ref = Reference(pattern)
    rows = accept = shape = 0
    for a in answers:
        want = ref.packed_columns(a.text)
        got = pack_columns(a.columns)
        if got.shape != want.shape:
            shape += 1
            continue
        rows += int((got != want).any(axis=1).sum())
        accept += int(a.accepted != bool(want[-1].any()))
    log(
        f"reference: {len(answers)} answers, "
        f"{sum(len(a.text) for a in answers)} bytes, {time.perf_counter() - t0:.3f} s"
    )
    return {"rows_differ": rows, "accept_differ": accept, "shape_differ": shape}


def _metric_line(specs: List[dict], values: Dict[str, float]) -> Dict[str, dict]:
    return {
        m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
        for m in specs if values.get(m["name"]) is not None
    }


def execute(cell: Cell, seed: int, seconds: float, trace: bool, t_start: float,
            devices, log, wrap_parser: Optional[Callable] = None) -> dict:
    """Drive one run after the device check; returns the result object.

    ``wrap_parser`` replaces the system under test (the control, in
    ``harness/control.py`` and the tests); a benchmark run passes none."""
    from . import device as dev
    from .compile_clock import CompileClock

    clock = CompileClock().install()
    run = Run(cell, seed, seconds, trace, t_start, devices, clock, log, wrap_parser)
    run.mark("imports and device init")
    out: Outcome = cell.kind.run(run)
    if run.setup_s is None:
        raise CellError(f"kind {cell.traffic['kind']} never opened its window")
    gc.collect()                   # the program's state is freed before the check

    checks = compare(out.answers, cell.config["regex"], log)
    checks["missing"] = out.failed
    checks["unchecked"] = 0 if out.answers else 1
    limits = {name: 0 for name in checks}
    correct = all(checks[k] <= limits[k] for k in checks)

    result: Dict[str, Any] = {
        "correct": correct,
        "attempted": out.attempted,
        "failed": out.failed,
    }
    device = dev.describe(devices)
    device["memory_peak_bytes"] = out.memory_peak_bytes
    if trace:
        readers = {
            m["name"]: load_module(BENCH / "metrics" / f"{m['name']}.py")
            for m in cell.per_layer
        }
        values = {name: readers[name].read(out.layer_data) for name in readers}
        result["metrics"] = _metric_line(cell.per_layer, values)
        prof = out.layer_data.get("profile")
        if prof is not None:
            device["busy_s"] = prof.busy_s
            device["window_s"] = prof.window_s
            result["breakdown"] = prof.breakdown()
    else:
        values = dict(out.end_to_end, setup_s=run.setup_s)
        result["metrics"] = _metric_line(cell.end_to_end, values)
    result["device"] = device
    result["checks"] = {k: {"value": checks[k], "limit": limits[k]} for k in checks}
    for k in checks:
        log(f"check {k}: {checks[k]} (limit {limits[k]})")
    return result


def _json_safe(x):
    if isinstance(x, float) and not math.isfinite(x):
        return None
    if isinstance(x, dict):
        return {k: _json_safe(v) for k, v in x.items()}
    if isinstance(x, (list, tuple)):
        return [_json_safe(v) for v in x]
    return x


def main(argv: Optional[List[str]] = None, *, t_start: Optional[float] = None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(prog="bench/run.py", description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    def log(msg: str) -> None:
        print(f"[bench {args.workload}] {msg}", file=sys.stderr, flush=True)

    try:
        cell = load_cell(args.workload)
    except (CellError, KeyError, ValueError) as e:
        log(f"error: {e}")
        return 2
    if not (ROOT / "src" / "repro").is_dir():
        log(f"error: {ROOT} holds no parser (src/repro is missing)")
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    from . import device as dev

    try:
        devices = dev.check_devices(cell.chips)
        dev.peaks_of(devices[0].device_kind)
    except dev.DeviceError as e:
        log(f"error: {e}")
        return 1
    log(f"device {dev.describe(devices)}; compile cache {cache_dir}")
    result = execute(cell, args.seed, args.seconds, bool(args.trace), t_start, devices, log)
    print(json.dumps(_json_safe(result)), flush=True)
    return 0
