"""The program's own host spans, per round of a TAG round cell.

The program names each span once: ``<role>/<what>`` on a role's thread
(``<what>`` a tasklet's alias, ``recv`` or ``send``) and ``fold/<step>``
inside the streaming fold. A round is one ``global-aggregator/aggregate``
tasklet span in the window. Where the program opens no such span, as before
it had any, every reader here finds nothing and gives ``None``.

The spans are read on the profiler's one clock beside device 0's
operations, so a round is counted only in a trace that holds device 0: a
host-only trace (a run on the CPU) gives ``None`` too.
"""
from __future__ import annotations

from typing import Iterable, List, Optional

from chipbench.trace import (DEVICE_PLANE, Interval, Trace, clip, host_spans,
                             measure, spans_of)

SERVER = "global-aggregator/"
ROUND = SERVER + "aggregate"
CHANNEL = (SERVER + "recv", SERVER + "send")


def rounds(trace: Optional[Trace]) -> int:
    if trace is None or 0 not in trace.devices:
        return 0
    return trace.span_count(ROUND)


def in_window(trace: Trace, names: Iterable[str]) -> List[Interval]:
    """The spans named ``names``, clipped to the window."""
    spans = [s for n in names for s in spans_of(host_spans(trace.events, n))]
    return clip(spans, [trace.window])


def server_tasklets(trace: Trace) -> List[str]:
    """The global aggregator's tasklet spans other than its ``aggregate``:
    the TAG runtime's share of the server's thread."""
    names = {e.name for e in trace.events
             if e.name.startswith(SERVER) and not DEVICE_PLANE.match(e.plane)}
    return sorted(names - {ROUND, *CHANNEL})


def per_round_ms(trace: Optional[Trace], names: Iterable[str]) -> Optional[float]:
    """Milliseconds a round inside the spans ``names``: their union in the
    window over the rounds in it; ``None`` where either is missing."""
    n = rounds(trace)
    spans = in_window(trace, names) if n else []
    if not spans:
        return None
    return measure(spans) / n / 1e6
