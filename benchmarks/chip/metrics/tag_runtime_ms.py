"""tag_runtime_ms: the global aggregator's tasklets other than its
``aggregate`` (``distribute`` with its send, ``evaluate``,
``check_rounds``), per round: the TAG runtime's share of the server's
thread. Moves ``round_s``."""
from chipbench.program_spans import per_round_ms, server_tasklets


def read(run):
    if run.trace is None:
        return None
    return per_round_ms(run.trace, server_tasklets(run.trace))
