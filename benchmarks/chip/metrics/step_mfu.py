"""step_mfu: the FL train step's model FLOP utilisation: the model
operations of a round (``chipbench.moe_counts.round_flops``: every token of
the round trained, the held experts at their share of even routing, nothing
recomputed counted) over the round's time, ``round_s``, at the chip's bf16
peak. The count does not depend on the implementation. Moves ``round_s``."""
from chipbench.peaks import peak


def read(run):
    flops = run.counters.get("round_flops")
    round_s = run.metrics.get("round_s")
    if not flops or not round_s:
        return None
    return 100.0 * flops / round_s / peak(run.device_kind)["bf16_flops"]
