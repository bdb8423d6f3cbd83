"""idle_share.round: the share of the traced window in which device 0 ran
no operation, in a TAG round cell. Moves ``round_s``."""


def read(run):
    if run.trace is None:
        return None
    share = run.trace.idle_share(0)
    return None if share is None else 100.0 * share
