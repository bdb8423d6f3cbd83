"""agg_span_ms: the mean host-clock length of the benchmark's ``aggregate``
span per round: the fold with its host-device copies and the host division.
Moves ``round_s``."""


def read(run):
    spans = run.counters.get("agg_s")
    if not spans:
        return None
    return 1e3 * sum(spans) / len(spans)
