"""idle_unattributed.round: the share of the traced window in which device
0 runs no operation and none of the global aggregator's spans that account
for its time is open: ``fold/*``, ``global-aggregator/recv`` and ``/send``,
and its tasklets other than ``aggregate``. What the program's spans leave
unexplained of the idle chip. Moves ``round_s``."""
from chipbench.program_spans import CHANNEL, in_window, rounds, server_tasklets
from chipbench.trace import measure, subtract

FOLD = ("fold/scale", "fold/add", "fold/partial", "fold/finalize")


def read(run):
    t = run.trace
    if not rounds(t) or 0 not in t.devices:
        return None
    covered = t.ops(0) + in_window(t, FOLD + CHANNEL + tuple(server_tasklets(t)))
    lo, hi = t.window
    return 100.0 * measure(subtract([t.window], covered)) / (hi - lo)
