"""moe_dispatch_ms: device milliseconds a round in the expert layer's
routing, dispatch and combine: the operations of the program's
``moe/route``, ``moe/dispatch`` and ``moe/combine`` scopes over the
window's rounds. Moves ``round_s``."""

SCOPES = ("moe/route", "moe/dispatch", "moe/combine")


def read(run):
    scopes = run.counters.get("scope_device_s") or {}
    if not run.counters.get("rounds") or not any(s in scopes for s in SCOPES):
        return None
    return 1e3 * sum(scopes.get(s, 0.0) for s in SCOPES) / run.counters["rounds"]
