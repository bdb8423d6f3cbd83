"""fold_add_ms: the streaming fold's add of each scaled update to the
accumulator, per round: its ``fold/add`` spans (on the fused path, the
accumulator's round trip through the host, or the first update's pull to
the host). Moves ``round_s``."""
from chipbench.program_spans import per_round_ms


def read(run):
    return per_round_ms(run.trace, ["fold/add"])
