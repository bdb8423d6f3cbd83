"""agg_recv_wait_ms: the global aggregator's wait for, and take of, the
round's update frames, per round: its ``global-aggregator/recv`` spans.
The transport layer (``inproc`` channel). Moves ``round_s``."""
from chipbench.program_spans import per_round_ms


def read(run):
    return per_round_ms(run.trace, ["global-aggregator/recv"])
