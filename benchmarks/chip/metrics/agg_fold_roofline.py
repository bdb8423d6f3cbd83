"""agg_fold_roofline: the aggregation fold's share of its HBM roofline.

The least bytes of each round's fold (every update read once, the mean
written once: ``chipbench.counts.fold_least_bytes``) at the chip's HBM
bandwidth, over the device time of all operations inside the benchmark's
``aggregate`` spans. The count does not depend on the implementation.
Moves ``round_s``.
"""
from chipbench.peaks import peak


def read(run):
    if run.trace is None or "least_bytes" not in run.counters:
        return None
    busy = run.trace.device_time_in("aggregate", 0)
    spans = run.trace.span_count("aggregate")
    if not busy or not spans:
        return None
    least_s = spans * run.counters["least_bytes"] / peak(run.device_kind)["hbm_bytes_per_s"]
    return 100.0 * least_s / busy
