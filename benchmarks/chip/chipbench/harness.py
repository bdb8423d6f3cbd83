"""One run of one cell: the driver of its traffic kind, the check of its
numbers against their limits, the readers of its per-layer metrics, and
the result line.
"""
from __future__ import annotations

import contextlib
import dataclasses
import glob
import importlib
import shutil
import sys
import tempfile
from typing import Any, Dict, List, Optional

from chipbench import compare
from chipbench.manifest import Cell, Manifest
from chipbench.trace import Trace, load_xplane


@dataclasses.dataclass
class Outcome:
    """What a cell's driver hands back."""

    metrics: Dict[str, float]  # end-to-end, by name
    attempted: int
    failed: int
    numbers: Dict[str, float]  # compared against the cell's limits
    counters: Dict[str, Any]  # what per-layer readers may read
    memory_peak_bytes: int
    trace: Optional[Trace] = None


@dataclasses.dataclass
class Run:
    """What a per-layer reader sees."""

    cell: Cell
    device_kind: str
    chips: int
    metrics: Dict[str, float]
    counters: Dict[str, Any]
    trace: Optional[Trace]


@contextlib.contextmanager
def profile(enabled: bool):
    """Trace the enclosed block with the JAX profiler into a fresh temporary
    directory; yields a list that holds the reduced ``Trace`` afterwards.
    The raw trace is deleted once read."""
    out: List[Trace] = []
    if not enabled:
        yield out
        return
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    logdir = tempfile.mkdtemp(prefix="chipbench-trace-")
    try:
        jax.profiler.start_trace(logdir, profiler_options=opts)
        try:
            yield out
        finally:
            jax.profiler.stop_trace()
        paths = glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True)
        if len(paths) != 1:
            raise RuntimeError(f"expected one trace file, found {paths}")
        out.append(Trace.from_events(load_xplane(paths[0])))
    finally:
        shutil.rmtree(logdir, ignore_errors=True)


def memory_peak(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def driver(kind: str):
    return importlib.import_module(f"chipbench.cells.{kind}")


def run_workload(manifest: Manifest, name: str, seed: int, seconds: float,
                 trace: bool, devices, t0: float) -> dict:
    """Run cell ``name`` on ``devices`` and return the result line's object.
    ``t0`` is the process's start on ``time.perf_counter``'s clock."""
    cell = manifest.cell(name)
    used = list(devices)[: cell.chips]
    out = driver(cell.traffic["kind"]).run(cell, seed, seconds, trace, used, t0)
    correct, checks = compare.judge(out.numbers, cell.limits)
    dev = used[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(used), "memory_peak_bytes": out.memory_peak_bytes}
    result: Dict[str, Any] = {"correct": bool(correct), "attempted": out.attempted,
                              "failed": out.failed}
    if trace:
        run = Run(cell, dev.device_kind, len(used), out.metrics, out.counters,
                  out.trace)
        metrics = {}
        for m in cell.per_layer:
            value = manifest.reader(m["name"])(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        result["metrics"] = metrics
        if out.trace is not None:
            device["busy_s"] = out.trace.busy_s()
            device["window_s"] = out.trace.window_s
            result["breakdown"] = out.trace.breakdown()
    else:
        result["metrics"] = {
            m["name"]: {"value": out.metrics[m["name"]], "unit": m["unit"]}
            for m in cell.end_to_end
        }
    result["device"] = device
    result["checks"] = checks
    for k, v in checks.items():
        print(f"check {k}: {v['value']} (limit {v['limit']})", file=sys.stderr)
    return result
