"""fold_scale_ms: the streaming fold's scale of each update, per round:
its ``fold/scale`` spans (on the fused path, the update's copy to the
device and the jitted scale). Moves ``round_s``."""
from chipbench.program_spans import per_round_ms


def read(run):
    return per_round_ms(run.trace, ["fold/scale"])
