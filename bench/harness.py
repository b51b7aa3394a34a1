"""Run one cell of ``BENCHMARK.json`` once and print the result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell names a configuration and a traffic mix.  Everything about them is
data, found by name: ``bench/configs/<config>.json`` (with its plain
reference beside it, ``<config>.py``), ``bench/traffic/<traffic>.json``,
and one reader per per-layer metric, ``bench/metrics/<metric>.py``.  The
configuration names the driver (``bench/drivers/<driver>.py``) that runs
its kind of system: a new cell, configuration, mix or per-layer metric is
new files and entries, never an edit.

A run: find the chips (a TPU, as many as the cell asks for, or exit
non-zero), turn on the program's compile cache, let the driver build the
system from the seed and warm up every shape (``setup_s``), measure for
``--seconds`` (under the profiler with ``--trace 1``), read the peak
device memory, free the program's state, and compare a seeded sample of
what the window produced with the plain reference.  The compared numbers
go to standard error as the last lines, and the JSON result, whose
``checks`` key comes last, is the last line of standard output.
"""
from __future__ import annotations

import argparse
import dataclasses
import importlib
import importlib.util
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
SPEC = ROOT / "BENCHMARK.json"


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]


@dataclasses.dataclass
class Check:
    """One compared number and its limit; a run is correct when every
    value is at or under its limit (at or over it, for ``least``)."""
    name: str
    value: float
    limit: float
    least: bool = False

    @property
    def ok(self) -> bool:
        return self.value >= self.limit if self.least else \
            self.value <= self.limit


@dataclasses.dataclass
class Window:
    """What a driver's measured window produced (host clock, seconds)."""
    t0: float
    t1: float
    end_to_end: Dict[str, float]
    attempted: int
    failed: int = 0
    counts: Dict[str, float] = dataclasses.field(default_factory=dict)
    spans: Dict[str, List[float]] = dataclasses.field(default_factory=dict)
    notes: List[str] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class Reading:
    """What a per-layer metric's reader gets: the window, the reduced
    device trace, the cell's data and the chip's peaks."""
    cell: Cell
    window: Window
    trace: Any
    peaks: Dict[str, float]


def load_json(path: Path) -> Any:
    return json.loads(Path(path).read_text())


def load_module(path: Path, name: Optional[str] = None):
    """Import a file by path (names with dots or dashes included)."""
    spec = importlib.util.spec_from_file_location(
        name or f"bench_{path.stem.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(name: str, spec_path: Path = SPEC,
              bench_dir: Path = BENCH) -> Cell:
    spec = load_json(spec_path)
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no cell {name!r} in {spec_path.name} "
                       f"(have {sorted(cells)})")
    w = cells[name]
    entry = {c["name"]: c for c in spec["configs"]}[w["config"]]
    config = load_json(spec_path.parent / entry["file"])
    traffic = load_json(bench_dir / "traffic" / f"{w['traffic']}.json")
    e2e = [m for m in spec["end_to_end"]
           if name in m.get("workloads", [name])]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in reported)]
    return Cell(name, int(w["chips"]), config, traffic, e2e, per_layer)


def reference(config: Dict[str, Any], bench_dir: Path = BENCH):
    """The configuration's plain reference, ``configs/<name>.py``."""
    return load_module(bench_dir / "configs" / f"{config['name']}.py")


def driver(config: Dict[str, Any]):
    return importlib.import_module(f"bench.drivers.{config['driver']}")


def peaks_of(kind: str, bench_dir: Path = BENCH) -> Dict[str, float]:
    table = load_json(bench_dir / "peaks.json")["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json "
                       f"(have {sorted(table)})")
    return table[kind]


def find_chips(chips: int) -> list:
    """The first ``chips`` TPU devices, or :class:`NoChip`: there is no
    CPU fallback."""
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise NoChip(f"JAX found no usable backend: {e}") from None
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX's devices are {devices[0].platform!r} "
                     f"({len(devices)} of them); this benchmark runs on a "
                     f"TPU only")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} TPU chips, JAX sees "
                     f"{len(devices)}")
    return devices[:chips]


def enable_cache() -> str:
    import jax

    from repro.runtime.compile_cache import enable_compile_cache
    path = enable_compile_cache()
    # every program, small ones included, so that a second run compiles
    # nothing and its set-up is steady
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


def per_layer_metrics(reading: Reading,
                      bench_dir: Path = BENCH) -> Dict[str, Dict[str, Any]]:
    """Each of the cell's per-layer metrics, from its own reader; a reader
    that finds nothing to read returns None and the metric is left out."""
    out = {}
    for m in reading.cell.per_layer:
        read: Callable = load_module(
            bench_dir / "metrics" / f"{m['name']}.py").read
        value = read(reading)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def end_to_end_metrics(cell: Cell, window: Window,
                       setup_s: float) -> Dict[str, Dict[str, Any]]:
    values = dict(window.end_to_end, setup_s=setup_s)
    return {m["name"]: {"value": float(values[m["name"]]), "unit": m["unit"]}
            for m in cell.end_to_end}


def memory_peak(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


class CompileLog:
    """Times at which JAX compiled or fetched a program from its cache, to
    count those inside the measured window (there should be none)."""

    def __init__(self):
        import jax

        self.times: List[float] = []
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event: str, duration: float, **kw) -> None:
        if "compile" in event or "cache_retrieval" in event:
            self.times.append(time.perf_counter())

    def inside(self, t0: float, t1: float) -> int:
        return sum(1 for t in self.times if t0 <= t <= t1)


def run_cell(cell: Cell, *, seed: int, seconds: float, trace: bool,
             t_start: float, devices, peaks: Dict[str, float],
             dump_trace: Optional[Path] = None,
             log: Callable[[str], None] = print,
             compiles: Optional[CompileLog] = None) -> Dict[str, Any]:
    """Set up, measure, check; returns the result object (without
    printing).  ``devices`` are the chips the cell runs on and ``peaks``
    theirs; tests pass the CPU's, which skips only the look for a chip."""
    from bench import trace as tr

    drv = driver(cell.config).Driver(cell.config, cell.traffic, seed=seed,
                                     devices=devices, log=log,
                                     tracing=trace)
    setup_s = time.perf_counter() - t_start
    log(f"[setup] {setup_s:.3f} s")
    logdir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    try:
        with tr.profiled(logdir):
            window = drv.window(seconds)
        for note in window.notes:
            log(note)
        if compiles is not None:
            log(f"[window] programs compiled or loaded inside the window: "
                f"{compiles.inside(window.t0, window.t1)}")
        reduced = tr.load_dir(logdir) if trace else None
        if trace and dump_trace is not None:
            dump_trace.parent.mkdir(parents=True, exist_ok=True)
            dump_trace.write_text(json.dumps(
                dict(tr.to_json(reduced), planes=tr.describe_dir(logdir))))
    finally:
        if logdir:
            shutil.rmtree(logdir, ignore_errors=True)
    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices), "memory_peak_bytes": memory_peak(devices)}
    drv.release()
    checks = drv.check()
    result: Dict[str, Any] = {
        "correct": all(c.ok for c in checks),
        "attempted": int(window.attempted),
        "failed": int(window.failed),
    }
    if trace:
        reading = Reading(cell, window, reduced, peaks)
        result["metrics"] = per_layer_metrics(reading)
        device["busy_s"] = tr.busy_s(reduced)
        device["window_s"] = tr.window_s(reduced)
        result["breakdown"] = tr.breakdown(reduced)
    else:
        result["metrics"] = end_to_end_metrics(cell, window, setup_s)
    result["device"] = device
    result["checks"] = {c.name: dict({"value": c.value, "limit": c.limit},
                                     **({"at_least": True} if c.least else {}))
                        for c in checks}
    return result


def main(argv=None, t_start: Optional[float] = None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--dump-trace", type=Path, default=None,
                    help="also write the reduced device trace as JSON")
    args = ap.parse_args(argv)

    def log(msg: str) -> None:
        print(msg, flush=True)

    cell = load_cell(args.workload)
    try:
        devices = find_chips(cell.chips)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr, flush=True)
        return 2
    peaks = peaks_of(devices[0].device_kind)  # an unknown chip is an error
    log(f"[device] {devices[0].device_kind} x{len(devices)} ids="
        f"{[int(d.id) for d in devices]} cache={enable_cache()}")
    result = run_cell(cell, seed=args.seed, seconds=args.seconds,
                      trace=bool(args.trace), t_start=t_start,
                      devices=devices, peaks=peaks,
                      dump_trace=args.dump_trace, log=log,
                      compiles=CompileLog())
    for name, c in result["checks"].items():
        bound = "at least" if c.get("at_least") else "limit"
        print(f"check {name}: {c['value']} ({bound} {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0
