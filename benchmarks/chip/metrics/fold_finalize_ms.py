"""fold_finalize_ms: the streaming fold's division of the sum by the
round's sample total on the host, per round: its ``fold/finalize`` spans.
Moves ``round_s``."""
from chipbench.program_spans import per_round_ms


def read(run):
    return per_round_ms(run.trace, ["fold/finalize"])
