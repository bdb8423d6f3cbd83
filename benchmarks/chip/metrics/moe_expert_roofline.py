"""moe_expert_roofline: the held experts' grouped matrix products' share of
the chip's bf16 peak: the training operations of the picks the step
counted on held experts in the window (``held_picks`` times
``expert_pick_flops``) over the device time of the operations in the
program's ``moe/experts`` scope, at the bf16 peak. Moves ``round_s``."""
from chipbench.peaks import peak


def read(run):
    busy = (run.counters.get("scope_device_s") or {}).get("moe/experts")
    if not busy or "held_picks" not in run.counters:
        return None
    flops = run.counters["held_picks"] * run.counters["expert_pick_flops"]
    return 100.0 * flops / busy / peak(run.device_kind)["bf16_flops"]
