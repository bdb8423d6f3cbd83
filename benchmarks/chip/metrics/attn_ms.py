"""attn_ms: device milliseconds a round in attention: the operations of
the program's ``attn/proj`` and ``attn/core`` scopes over the window's
rounds. Moves ``round_s``."""

SCOPES = ("attn/proj", "attn/core")


def read(run):
    scopes = run.counters.get("scope_device_s") or {}
    if not run.counters.get("rounds") or not any(s in scopes for s in SCOPES):
        return None
    return 1e3 * sum(scopes.get(s, 0.0) for s in SCOPES) / run.counters["rounds"]
