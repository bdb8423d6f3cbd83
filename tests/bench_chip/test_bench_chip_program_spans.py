"""The readers of the program's own spans, on a small trace whose answers
are worked out by hand (``fixtures/program_spans.json``), and on the trace
of a CPU-sized cell run through its driver.

Window [0, 1000] ns, two rounds: ``global-aggregator/aggregate`` [100, 400]
and [600, 900]. Inside them the server's ``recv`` [110, 130], [200, 210],
[610, 640]; ``fold/scale`` [130, 160], [210, 250], [640, 700]; ``fold/add``
[160, 190], [250, 300], [700, 780]; ``fold/finalize`` [300, 380],
[780, 880]. Its other tasklets: ``distribute`` [50, 100], [550, 600] (each
holding a ``send``), ``evaluate`` [400, 410], [900, 910], ``check_rounds``
[410, 420], [910, 1010]. Device 0's ops: [140, 160], [190, 200],
[260, 290], [450, 470], [650, 700], [990, 1100]. A trainer's spans run
beside them.
"""
import json
import time
from pathlib import Path

import jax
import pytest

from bench_chip_cells import TAG_TRAFFIC
from chipbench.harness import Run, driver
from chipbench.manifest import Manifest
from chipbench.trace import Event, Trace

FIXTURE = json.loads((Path(__file__).parent / "fixtures" / "program_spans.json").read_text())
NS = 1e-6  # ms

# per round: recv 20 + 10 + 30; scale 30 + 40 + 60; add 30 + 50 + 80;
# finalize 80 + 100; tasklets 50 + 50 (distribute) + 10 + 10 (evaluate) +
# 10 + 90 (check_rounds, the second cut at the window's end)
WANT = {
    "agg_recv_wait_ms": 60 / 2 * NS,
    "fold_scale_ms": 130 / 2 * NS,
    "fold_add_ms": 160 / 2 * NS,
    "fold_finalize_ms": 180 / 2 * NS,
    "tag_runtime_ms": 220 / 2 * NS,
    # uncovered: [0, 50], [100, 110], [380, 400], [420, 450], [470, 550],
    # [600, 610], [880, 900]
    "idle_unattributed.round": 22.0,
}
SPAN_READERS = ["agg_recv_wait_ms", "fold_scale_ms", "fold_add_ms",
                "fold_finalize_ms", "tag_runtime_ms"]


def _run(events=None):
    trace = None if events is None else Trace.from_events(
        [Event(*e) for e in events])
    return Run(None, "TPU v5 lite", 1, {}, {}, trace)


def _read(name, run):
    return Manifest().reader(name)(run)


@pytest.mark.parametrize("name", sorted(WANT))
def test_each_reader_gives_the_hand_worked_value(name):
    assert _read(name, _run(FIXTURE["events"])) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", sorted(WANT))
def test_each_reader_finds_nothing_where_the_program_records_nothing(name):
    # a program without spans: the window and the device's ops alone
    bare = [e for e in FIXTURE["events"]
            if e[2] == "window" or e[0].startswith("/device:")]
    assert _read(name, _run(bare)) is None
    assert _read(name, _run()) is None


@pytest.mark.parametrize("name", sorted(WANT))
def test_each_reader_finds_nothing_in_a_trace_without_the_device(name):
    host = [e for e in FIXTURE["events"] if not e[0].startswith("/device:")]
    assert _read(name, _run(host)) is None


def test_a_cpu_run_of_the_cell_records_the_program_spans(tiny_root):
    """The tiny cell's driver, traced on the CPU: its trace holds one
    aggregate tasklet per round and one fold and one receive per update;
    the readers give nothing there, as the trace holds no device, and
    read the same spans once device 0 is in it."""
    cell = Manifest(tiny_root).cell("tag.tiny")
    out = driver("tag_round").run(cell, 2**33 + 7, 0.5, True, jax.devices()[:1],
                                  time.perf_counter())
    trace = out.trace
    rounds = out.attempted
    updates = TAG_TRAFFIC["trainers"] * rounds
    assert trace.span_count("global-aggregator/aggregate") == rounds
    assert trace.span_count("fold/scale") == updates
    assert trace.span_count("global-aggregator/recv") == updates
    assert trace.span_count("fold/finalize") == rounds
    for name in WANT:
        assert _read(name, Run(cell, "cpu", 1, out.metrics, out.counters, trace)) is None
    lo, hi = trace.window
    op = Event("/device:TPU:0", "XLA Ops", "add.1 f32[8]", lo, 1.0)
    on_chip = Run(cell, "cpu", 1, out.metrics, out.counters,
                  Trace.from_events(trace.events + [op]))
    for name in SPAN_READERS:
        assert _read(name, on_chip) > 0, name
    assert 0 <= _read("idle_unattributed.round", on_chip) < 100
