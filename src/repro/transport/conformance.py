"""Shared transport-conformance suite.

Every ``TransportBackend`` implementation — inproc, mqtt-emu, p2p-emu,
multiproc — must pass the same semantics checks; ``tests/
test_transport_conformance.py`` parametrizes this suite over all registered
backends plus a live ``TransportHub``. Keeping the checks in the library (not
the test tree) means worker *processes* can import the reference programs
(classes defined inside a test function would not survive a ``spawn``
pickle), and downstream backends get the suite for free.

Each check takes a zero-argument ``factory`` producing a **fresh** backend
and raises ``AssertionError`` (or an unexpected exception) on a conformance
violation. Checks that assert exact clock arithmetic expect virtual-clock
semantics — run hubs with ``wall_clock=False`` here; the wall-clock mapping
is exercised by the end-to-end multiproc job tests.
"""
from __future__ import annotations

import queue
import threading
import time
from typing import Callable, Dict, List, Optional, Sequence

import numpy as np

from repro.core.channels import (
    TRANSPORT_OPS,
    ChannelEnd,
    LinkModel,
    TransportBackend,
    WorkerDropped,
    recv_any_multi,
)
from repro.core.roles import Trainer

Factory = Callable[[], TransportBackend]

CH, G = "conf-ch", "default"


def _pair(backend: TransportBackend, a: str = "a-0", b: str = "b-0"):
    backend.join(CH, G, a)
    backend.join(CH, G, b)
    return (
        ChannelEnd(backend, CH, G, a),
        ChannelEnd(backend, CH, G, b),
    )


# ------------------------------------------------------------------ #
# checks
# ------------------------------------------------------------------ #
def check_protocol_surface(factory: Factory) -> None:
    """Every protocol op exists and is callable; name/stats attributes too."""
    be = factory()
    for op in TRANSPORT_OPS:
        assert callable(getattr(be, op, None)), f"missing transport op {op!r}"
    assert isinstance(be.name, str) and be.name
    assert hasattr(be, "stats")


def check_send_recv_roundtrip(factory: Factory) -> None:
    """A nested pytree with float32 arrays round-trips bit-exactly."""
    be = factory()
    ea, eb = _pair(be)
    payload = {
        "weights": {
            "w": np.arange(12, dtype=np.float32).reshape(3, 4) * np.float32(0.1),
            "b": np.zeros((4,), np.float32),
        },
        "num_samples": 7,
        "tags": ["x", "y"],
        "done": False,
        "version": None,
    }
    ea.send("b-0", payload)
    got = eb.recv("a-0")
    assert got["num_samples"] == 7 and got["done"] is False and got["version"] is None
    assert got["tags"] == ["x", "y"]
    assert np.asarray(got["weights"]["w"]).tobytes() == payload["weights"]["w"].tobytes()
    assert np.asarray(got["weights"]["b"]).dtype == np.float32


def check_membership(factory: Factory) -> None:
    be = factory()
    for w in ("a-0", "a-1", "b-0"):
        be.join(CH, G, w)
    be.join(CH, G, "a-0")  # double join is idempotent
    assert sorted(be.peers(CH, G, "b-0")) == ["a-0", "a-1"]
    assert sorted(be.peers(CH, G, "a-0")) == ["a-1", "b-0"]
    be.leave(CH, G, "a-1")
    assert sorted(be.peers(CH, G, "b-0")) == ["a-0"]
    # role filtering through ChannelEnd
    end = ChannelEnd(be, CH, G, "b-0", peer_role="a")
    assert end.ends() == ["a-0"]


def check_fifo_order(factory: Factory) -> None:
    """recv_fifo yields in emulated-arrival order and advances the clock."""
    be = factory()
    be.set_link(CH, "a-0", LinkModel(latency=5.0))
    be.set_link(CH, "a-1", LinkModel(latency=2.0))
    for w in ("a-0", "a-1", "b-0"):
        be.join(CH, G, w)
    # fast sender first so the expected order also holds under a contended
    # shared-broker model (serialization can only push a-0 later)
    be.send(CH, G, "a-1", "b-0", "fast")
    be.send(CH, G, "a-0", "b-0", "slow")
    got = list(be.recv_fifo(CH, G, "b-0", ["a-0", "a-1"], timeout=5.0))
    assert got == [("a-1", "fast"), ("a-0", "slow")]
    assert be.now("b-0") >= 5.0


def check_peek_nonblocking(factory: Factory) -> None:
    be = factory()
    ea, eb = _pair(be)
    assert eb.peek("a-0") is None
    ea.send("b-0", 42)
    assert eb.peek("a-0") == 42  # non-consuming
    assert eb.recv("a-0") == 42
    assert eb.peek("a-0") is None


def check_earliest_empty_ends(factory: Factory) -> None:
    """``earliest`` over no ends / empty mailboxes is None, never an error."""
    be = factory()
    ea, eb = _pair(be)
    assert eb.earliest([]) is None
    assert eb.earliest(["a-0"]) is None  # joined, nothing sent
    assert eb.earliest(["ghost-7"]) is None  # never joined at all
    be.set_link(CH, "a-0", LinkModel(latency=3.0))
    ea.send("b-0", "x")
    got = eb.earliest(["a-0", "ghost-7"])
    assert got is not None
    arrival, end = got
    assert end == "a-0" and arrival >= 3.0
    # non-consuming: the message is still there
    assert eb.recv("a-0") == "x"


def check_recv_timeout_empty(factory: Factory) -> None:
    be = factory()
    _, eb = _pair(be)
    t0 = time.monotonic()
    try:
        eb.recv("a-0", timeout=0.1)
    except queue.Empty:
        pass
    else:
        raise AssertionError("recv on an empty mailbox must raise queue.Empty")
    try:
        eb.recv_any(["a-0"], timeout=0.1)
    except queue.Empty:
        pass
    else:
        raise AssertionError("recv_any on empty mailboxes must raise queue.Empty")
    assert time.monotonic() - t0 < 5.0


def check_recv_any_picks_earliest(factory: Factory) -> None:
    be = factory()
    be.set_link(CH, "a-0", LinkModel(latency=9.0))
    be.set_link(CH, "a-1", LinkModel(latency=1.0))
    for w in ("a-0", "a-1", "b-0"):
        be.join(CH, G, w)
    be.send(CH, G, "a-1", "b-0", "early")
    be.send(CH, G, "a-0", "b-0", "late")
    end, payload, arrival = be.recv_any(CH, G, "b-0", ["a-0", "a-1"], timeout=5.0)
    assert (end, payload) == ("a-1", "early")
    assert arrival >= 1.0
    # advance=False leaves the receiver clock untouched
    before = be.now("b-0")
    end, payload, arrival = be.recv_any(
        CH, G, "b-0", ["a-0", "a-1"], timeout=5.0, advance=False
    )
    assert (end, payload) == ("a-0", "late")
    assert be.now("b-0") == before


def check_poison_wakes_blocked_recv(factory: Factory) -> None:
    """poison() interrupts a receive already blocked in the transport."""
    be = factory()
    _, eb = _pair(be)
    caught: List[BaseException] = []
    started = threading.Event()

    def _blocked() -> None:
        started.set()
        try:
            eb.recv("a-0", timeout=30.0)
        except BaseException as exc:  # noqa: BLE001
            caught.append(exc)

    t = threading.Thread(target=_blocked, daemon=True)
    t.start()
    started.wait(5.0)
    time.sleep(0.2)  # let the receive actually block inside the transport
    be.poison("b-0", at=1.25)
    t.join(timeout=5.0)
    assert not t.is_alive(), "poison did not wake the blocked recv"
    assert len(caught) == 1 and isinstance(caught[0], WorkerDropped)
    assert caught[0].worker == "b-0" and caught[0].at == 1.25


def check_poison_wakes_recv_any_multi(factory: Factory) -> None:
    """poison() unblocks a cross-channel recv_any_multi promptly."""
    be = factory()
    be.join(CH, G, "b-0")
    be.join("conf-ch2", G, "b-0")
    end1 = ChannelEnd(be, CH, G, "b-0")
    end2 = ChannelEnd(be, "conf-ch2", G, "b-0")
    caught: List[BaseException] = []
    started = threading.Event()

    def _blocked() -> None:
        started.set()
        try:
            recv_any_multi([(end1, ["a-0"]), (end2, ["c-0"])], timeout=30.0)
        except BaseException as exc:  # noqa: BLE001
            caught.append(exc)

    t = threading.Thread(target=_blocked, daemon=True)
    t.start()
    started.wait(5.0)
    time.sleep(0.2)
    t0 = time.monotonic()
    be.poison("b-0", at=2.5)
    t.join(timeout=5.0)
    assert not t.is_alive(), "poison did not wake recv_any_multi"
    assert time.monotonic() - t0 < 3.0, "recv_any_multi woke too slowly"
    assert len(caught) == 1 and isinstance(caught[0], WorkerDropped)
    assert caught[0].at == 2.5


def check_dropout_mid_recv_fifo(factory: Factory) -> None:
    """A receiver whose dropout time precedes a message's arrival dies while
    consuming recv_fifo — not silently after it."""
    be = factory()
    be.set_link(CH, "a-0", LinkModel(latency=10.0))  # arrival at t=10
    ea, eb = _pair(be)
    be.set_drop("b-0", at=5.0)  # b-0 dies before the delivery completes
    ea.send("b-0", "never-seen")
    gen = eb.recv_fifo(["a-0"], timeout=5.0)
    try:
        list(gen)
    except WorkerDropped as exc:
        assert exc.worker == "b-0" and exc.at == 5.0
    else:
        raise AssertionError("recv_fifo ignored the receiver's dropout schedule")
    # the receiver's clock froze at the dropout time
    assert be.now("b-0") == 5.0


def check_dropout_on_send(factory: Factory) -> None:
    """A sender dying mid-transfer delivers nothing. Pipelined transports
    may defer the fault past the fire-and-forget send itself, but it must
    surface no later than the sender's next synchronous op (the barrier
    ``now`` below) — never silently retried or dropped."""
    be = factory()
    be.set_link(CH, "a-0", LinkModel(bandwidth=10.0))  # 100B -> 10s transfer
    ea, eb = _pair(be)
    be.set_drop("a-0", at=4.0)
    try:
        ea.send("b-0", np.zeros(25, np.float32))
        be.now("a-0")  # ack barrier for pipelined sends
    except WorkerDropped as exc:
        assert exc.worker == "a-0" and exc.at == 4.0
    else:
        raise AssertionError("send ignored the sender's dropout schedule")
    assert eb.peek("a-0") is None
    be.clear_drop("a-0")
    ea.send("b-0", "ok")  # clear_drop revives the sender
    assert eb.recv("a-0") == "ok"


def check_clock_ops(factory: Factory) -> None:
    be = factory()
    be.join(CH, G, "a-0")
    assert be.now("a-0") == 0.0
    be.advance("a-0", 2.5)
    assert be.now("a-0") == 2.5
    be.set_clock("a-0", 1.0)  # never moves backwards
    assert be.now("a-0") == 2.5
    be.set_clock("a-0", 7.0)
    assert be.now("a-0") == 7.0
    assert be.drop_time("a-0") is None


def check_supervisor_rejoin_reset(factory: Factory) -> None:
    """The process-supervisor op sequence used by the event engine: a
    tripped ``set_drop`` freezes the worker; ``clear_drop`` + ``set_clock``
    re-admit it at the re-join time — drop schedule gone, poison cleared,
    clock moved forward, messaging live again."""
    be = factory()
    ea, eb = _pair(be)
    be.set_drop("b-0", at=1.0)
    assert be.drop_time("b-0") == 1.0
    try:
        be.advance("b-0", 2.0)
    except WorkerDropped as exc:
        assert exc.worker == "b-0" and exc.at == 1.0
    else:
        raise AssertionError("advance ignored the dropout schedule")
    # an orphan cascade may have poisoned the worker while it was down
    be.poison("b-0", at=1.0)
    # re-join: reset drop/poison state, move the clock to the re-join time
    be.clear_drop("b-0")
    assert be.drop_time("b-0") is None
    be.check_poison("b-0")  # clear_drop clears poison too: must not raise
    be.set_clock("b-0", 3.0)
    assert be.now("b-0") == 3.0
    ea.send("b-0", "welcome-back")
    assert eb.recv("a-0") == "welcome-back"


def check_stats_accounting(factory: Factory) -> None:
    """Byte/message accounting honors the channel wire dtype."""
    be = factory()
    be.set_wire_dtype(CH, "bf16")
    ea, _ = _pair(be)
    ea.send("b-0", {"w": np.zeros((10, 10), np.float32)})
    stats = dict(be.stats)
    assert stats.get(f"bytes:{CH}") == 200.0  # 100 elements x 2 bytes
    assert stats.get(f"msgs:{CH}") == 1.0


# ------------------------------------------------------------------ #
# send_many (broadcast fan-out) checks
# ------------------------------------------------------------------ #
_SM_DSTS = ("b-0", "b-1", "b-2")


def _fanout_setup(be: TransportBackend) -> None:
    for w in ("a-0", *_SM_DSTS, "c-0"):
        be.join(CH, G, w)


def _wire_stats(stats: Dict[str, float], channel: str) -> Dict[str, float]:
    """``channel``'s accounting keys, normalized to their prefix, that must
    match the per-dst send loop exactly. (``payload_encodes:`` deliberately
    excluded — fewer encodes is the whole point of the fast path.)"""
    prefixes = ("bytes:", "msgs:", "raw_bytes:", "coded_bytes:")
    return {
        p: float(stats[p + channel]) for p in prefixes if (p + channel) in stats
    }


def check_send_many_delivery(factory: Factory) -> None:
    """send_many delivers the payload to exactly the given dst set."""
    be = factory()
    _fanout_setup(be)
    payload = {"w": np.arange(8, dtype=np.float32), "done": False}
    be.send_many(CH, G, "a-0", [], payload)  # empty dst list is a no-op
    be.send_many(CH, G, "a-0", list(_SM_DSTS), payload)
    for dst in _SM_DSTS:
        got = be.recv(CH, G, dst, "a-0", timeout=5.0)
        assert got["done"] is False
        assert np.asarray(got["w"]).tobytes() == payload["w"].tobytes(), dst
    # a joined member outside the dst list receives nothing
    assert be.peek(CH, G, "c-0", "a-0") is None
    for dst in _SM_DSTS:
        assert be.peek(CH, G, dst, "a-0") is None  # exactly one copy each


def check_send_many_fifo_interleave(factory: Factory) -> None:
    """send_many interleaves with plain sends in issue order per mailbox."""
    be = factory()
    _fanout_setup(be)
    be.send(CH, G, "a-0", "b-0", "first")
    be.send_many(CH, G, "a-0", ["b-0", "b-1"], "fanned")
    be.send(CH, G, "a-0", "b-0", "last")
    got = [be.recv(CH, G, "b-0", "a-0", timeout=5.0) for _ in range(3)]
    assert got == ["first", "fanned", "last"], got
    assert be.recv(CH, G, "b-1", "a-0", timeout=5.0) == "fanned"


def check_send_many_accounting(factory: Factory) -> None:
    """Clock arithmetic and byte accounting are bit-identical to the
    per-dst send loop: same sender clock, same per-dst arrivals, same
    bytes/msgs (and raw/coded bytes on coded transports). Each comparison
    run lives on its own channel with its own worker names, so the check
    stays exact when ``factory`` hands out clients of one shared hub."""
    payload = {"w": np.arange(25, dtype=np.float32)}  # 100B on the wire

    def _run(fanout: bool) -> tuple:
        tag = "many" if fanout else "loop"
        ch = f"conf-sm-{tag}"
        src = f"sma-{tag}"
        dsts = [f"smb{i}-{tag}" for i in range(3)]
        be = factory()
        be.set_link(ch, src, LinkModel(bandwidth=100.0, latency=1.0))
        for w in (src, *dsts):
            be.join(ch, G, w)
        if fanout:
            be.send_many(ch, G, src, dsts, payload)
        else:
            for dst in dsts:
                be.send(ch, G, src, dst, payload)
        arrivals = []
        for dst in dsts:
            got = be.earliest(ch, G, dst, [src])
            assert got is not None, dst
            arrivals.append(float(got[0]))
        return be.now(src), arrivals, _wire_stats(dict(be.stats), ch)

    clock_loop, arr_loop, stats_loop = _run(fanout=False)
    clock_many, arr_many, stats_many = _run(fanout=True)
    assert clock_many == clock_loop, (clock_many, clock_loop)
    assert arr_many == arr_loop, (arr_many, arr_loop)
    assert stats_many == stats_loop, (stats_many, stats_loop)


def check_send_many_stateful_fallback(factory: Factory) -> None:
    """A link-stateful codec (per-dst error-feedback residuals) must make
    send_many behave exactly like the per-dst send loop: per-dst payloads
    and accounting bit-identical across two consecutive fan-outs (the
    second send is where a shared-encode shortcut would corrupt per-link
    residual state)."""
    be_probe = factory()
    if getattr(be_probe, "set_codec", None) is None:
        return  # codec-free transport: nothing to fall back from

    payload = {"w": np.linspace(-1.0, 1.0, 64).astype(np.float32)}
    extra = {"w": (np.linspace(1.0, -1.0, 64) * 0.5).astype(np.float32)}

    def _run(fanout: bool) -> tuple:
        tag = "many" if fanout else "loop"
        ch = f"conf-tk-{tag}"
        src = f"tka-{tag}"
        dsts = [f"tkb0-{tag}", f"tkb1-{tag}"]
        be = factory()
        be.set_codec(ch, "topk0.25")
        for w in (src, *dsts):
            be.join(ch, G, w)

        def _take(dst: str) -> bytes:
            return np.asarray(be.recv(ch, G, dst, src, timeout=5.0)["w"]).tobytes()

        def _fan() -> None:
            if fanout:
                be.send_many(ch, G, src, dsts, payload)
            else:
                for dst in dsts:
                    be.send(ch, G, src, dst, payload)

        rounds = []
        # fan-out, then a dsts[0]-only send (residuals now DIVERGE per
        # dst), then a second fan-out whose per-dst payloads legitimately
        # differ — a shared-encode shortcut cannot reproduce the loop here
        _fan()
        rounds.append([_take(dst) for dst in dsts])
        be.send(ch, G, src, dsts[0], extra)
        rounds.append([_take(dsts[0])])
        _fan()
        rounds.append([_take(dst) for dst in dsts])
        return rounds, _wire_stats(dict(be.stats), ch)

    rounds_loop, stats_loop = _run(fanout=False)
    rounds_many, stats_many = _run(fanout=True)
    assert rounds_many == rounds_loop
    assert stats_many == stats_loop, (stats_many, stats_loop)


def check_install_reduce_fold(factory: Factory) -> None:
    """The reduce plane folds an incast broker-side with loop semantics.

    With a spec installed the dst receives ONE partial per shard — its
    accumulator bit-identical to a sorted-src ``StreamingMean`` fold of the
    same frames, its arrival the max of the folded arrivals — while the
    client-leg ``bytes:``/``msgs:`` accounting stays bit-identical to the
    unreduced incast. Installing is an absolute-state write (reinstall
    resets the round), non-update frames fall through to per-frame
    delivery, and an empty install uninstalls. Each comparison run lives on
    its own channel/workers so the check stays exact on shared-hub
    factories."""
    from repro.core.fold import StreamingMean
    from repro.transport.wire import is_hub_partial, reduce_src

    def _update(seed: int) -> dict:
        rng = np.random.default_rng(seed)
        return {
            "weights": {"w": rng.normal(size=(33,)).astype(np.float32)},
            "num_samples": 1 + seed % 3,
        }

    def _run(reduced: bool) -> tuple:
        tag = "on" if reduced else "off"
        ch = f"conf-rd-{tag}"
        dst = f"rdb-{tag}"
        srcs = sorted(f"rda{i}-{tag}" for i in range(3))
        be = factory()
        for s in srcs:
            be.set_link(ch, s, LinkModel(bandwidth=50.0, latency=2.0))
        for w in (dst, *srcs):
            be.join(ch, G, w)
        if reduced:
            be.install_reduce(ch, G, dst, srcs, 1)
        # reverse sorted order: the fold must buffer out-of-order arrivals
        # and still consume them sorted-src
        for n, s in enumerate(reversed(srcs)):
            be.send(ch, G, s, dst, _update(seed=len(srcs) - 1 - n))
            if reduced and n < len(srcs) - 1:
                # no partial may surface before the block completes
                assert be.earliest(ch, G, dst, [reduce_src(0)]) is None
        clocks = [be.now(s) for s in srcs]
        if reduced:
            got = be.earliest(ch, G, dst, [reduce_src(0)])
            assert got is not None
            arrivals = [float(got[0])]
            frames = [(reduce_src(0), be.recv(ch, G, dst, reduce_src(0), timeout=5.0))]
        else:
            arrivals, frames = [], []
            for s in srcs:
                got = be.earliest(ch, G, dst, [s])
                assert got is not None, s
                arrivals.append(float(got[0]))
                frames.append((s, be.recv(ch, G, dst, s, timeout=5.0)))
        return be, ch, dst, srcs, clocks, arrivals, frames, _wire_stats(dict(be.stats), ch)

    be, ch, dst, srcs, clocks_on, arr_on, frames_on, stats_on = _run(reduced=True)
    _, _, _, _, clocks_off, arr_off, _, stats_off = _run(reduced=False)

    # client-leg accounting and sender clocks identical to the unreduced loop
    assert clocks_on == clocks_off, (clocks_on, clocks_off)
    assert stats_on == stats_off, (stats_on, stats_off)
    # the partial arrives when its slowest constituent frame would have
    assert arr_on == [max(arr_off)], (arr_on, arr_off)

    (psrc, part), = frames_on
    assert is_hub_partial(part) and part["shard"] == 0 and psrc == reduce_src(0)
    assert part["srcs"] == srcs and part["count"] == len(srcs)
    ref = StreamingMean()
    for i, _ in enumerate(srcs):
        upd = _update(seed=i)
        ref.fold(upd["weights"], float(upd["num_samples"]))
    ref_acc, ref_total = ref.partial()
    assert float(part["num_samples"]) == ref_total
    assert np.asarray(part["acc"]["w"]).tobytes() == np.asarray(ref_acc["w"]).tobytes()

    stats = dict(be.stats)
    assert stats.get(f"hub_reduced:{ch}") == len(srcs), stats
    assert stats.get(f"hub_partials:{ch}") == 1, stats

    # reinstall is absolute-state: a half-folded round is discarded
    be.install_reduce(ch, G, dst, srcs, 1)
    be.send(ch, G, srcs[0], dst, _update(seed=0))
    be.install_reduce(ch, G, dst, srcs, 1)
    for i, s in enumerate(srcs):
        be.send(ch, G, s, dst, _update(seed=i))
    part2 = be.recv(ch, G, dst, reduce_src(0), timeout=5.0)
    assert part2["count"] == len(srcs)
    assert np.asarray(part2["acc"]["w"]).tobytes() == np.asarray(ref_acc["w"]).tobytes()

    # a non-update frame on the reduced topic must not be swallowed
    be.install_reduce(ch, G, dst, srcs, 1)
    be.send(ch, G, srcs[0], dst, {"hello": 1})
    assert be.recv(ch, G, dst, srcs[0], timeout=5.0) == {"hello": 1}

    # empty install uninstalls: next update is delivered per-frame
    be.install_reduce(ch, G, dst, [], 0)
    be.send(ch, G, srcs[1], dst, _update(seed=1))
    back = be.recv(ch, G, dst, srcs[1], timeout=5.0)
    assert not is_hub_partial(back) and "weights" in back


def check_install_reduce_sharded(factory: Factory) -> None:
    """A multi-shard plan partitions the incast per ``reduce_blocks`` —
    contiguous sorted blocks, one partial per shard, each fold sorted-src
    within its block — and is run-to-run deterministic: two identical runs
    produce byte-identical partials, and their shard-ordered combination
    matches the unreduced mean."""
    from repro.core.channels import reduce_blocks
    from repro.core.fold import StreamingMean
    from repro.transport.wire import reduce_src

    def _update(seed: int) -> dict:
        rng = np.random.default_rng(100 + seed)
        return {
            "weights": {"w": rng.normal(size=(17,)).astype(np.float32)},
            "num_samples": 2,
        }

    def _run(tag: str) -> list:
        ch = f"conf-rs-{tag}"
        dst = f"rsb-{tag}"
        srcs = sorted(f"rsa{i}-{tag}" for i in range(5))
        be = factory()
        for w in (dst, *srcs):
            be.join(ch, G, w)
        be.install_reduce(ch, G, dst, srcs, 2)
        for i, s in enumerate(srcs):
            be.send(ch, G, s, dst, _update(seed=i))
        blocks = reduce_blocks(srcs, 2)
        parts = [be.recv(ch, G, dst, reduce_src(i), timeout=5.0) for i in range(len(blocks))]
        for i, part in enumerate(parts):
            assert part["srcs"] == blocks[i], (part["srcs"], blocks[i])
            assert part["count"] == len(blocks[i])
        return parts

    parts_a = _run("r1")
    parts_b = _run("r2")
    for pa, pb in zip(parts_a, parts_b):
        assert np.asarray(pa["acc"]["w"]).tobytes() == np.asarray(pb["acc"]["w"]).tobytes()

    # shard-ordered combination == the unreduced mean of all five frames
    server = StreamingMean()
    for part in parts_a:
        server.fold_partial(part["acc"], part["num_samples"], count=part["count"])
    ref = StreamingMean()
    for i in range(5):
        upd = _update(seed=i)
        ref.fold(upd["weights"], float(upd["num_samples"]))
    mean_sharded, total_sharded = server.finalize()
    mean_flat, total_flat = ref.finalize()
    assert total_sharded == total_flat
    np.testing.assert_allclose(mean_sharded["w"], mean_flat["w"], rtol=1e-6)


# ------------------------------------------------------------------ #
# wire-codec conformance: every registered codec must round-trip these
# ------------------------------------------------------------------ #
def _codec_fixtures() -> List[object]:
    """Nested pytrees a codec must survive: model-update shapes, metadata
    scalars, empty/odd-sized arrays, deep nesting, and dicts colliding with
    the codec-envelope marker."""
    rng = np.random.default_rng(0)
    return [
        {
            "weights": {
                "w": rng.normal(size=(64, 32)).astype(np.float32),
                "b": rng.normal(size=(7,)).astype(np.float32),
            },
            "num_samples": 5,
            "version": 2,
            "done": False,
            "tags": ["x", "y"],
        },
        {
            "nested": [
                {"a": rng.normal(size=(5,)).astype(np.float32)},
                ({"b": rng.normal(size=(3, 3)).astype(np.float32)}, 1),
            ],
            "ints": np.arange(6, dtype=np.int64),
            "none": None,
        },
        {"empty": np.zeros((0,), np.float32), "scalar": np.float32(0.5)},
        {"odd": rng.normal(size=(4097,)).astype(np.float32) * 1e-3},
        # envelope-marker collision: must never be misread as an envelope
        {"__wire_codec__": "int8", "payload": {"x": 1}},
    ]


# per-codec internal sentinel shapes: user dicts with exactly these key sets
# must round-trip byte-exactly through *that* codec (escape machinery).
# Keys match codecs by name prefix.
CODEC_SENTINEL_FIXTURES: Dict[str, List[object]] = {
    "int8_blocks": [
        {"__qb__": 3},
        {"__qb_block_escape__": {"y": 2}},
    ],
    "int8": [
        {"__q8__": np.arange(4, dtype=np.int8), "__s8__": 0.5},
        {"__q8_escape__": {"x": 1}},
    ],
    "topk": [
        {
            "__tkv__": np.ones(2, np.float32),
            "__tki__": np.zeros(2, np.int32),
            "__tks__": (2,),
            "__tkd__": "<f4",
        },
        {"__tk_escape__": {"z": 1}},
    ],
}


def _float_absmax(tree: object) -> float:
    """Largest float-leaf magnitude in a pytree (0.0 when no floats)."""
    import jax

    out = 0.0
    for leaf in jax.tree_util.tree_leaves(tree):
        if getattr(getattr(leaf, "dtype", None), "kind", "") == "f" and np.size(leaf):
            out = max(out, float(np.abs(np.asarray(leaf)).max()))
    return out


def _assert_codec_tree(
    orig: object, back: object, codec: str, global_absmax: float = 0.0
) -> None:
    """Structure/dtype/shape preserved; non-float content exact; float
    leaves within the codec's loss envelope."""
    if isinstance(orig, dict):
        assert isinstance(back, dict) and set(back) == set(orig), (codec, orig, back)
        for k in orig:
            _assert_codec_tree(orig[k], back[k], codec, global_absmax)
        return
    if isinstance(orig, (list, tuple)):
        assert type(back) is type(orig) and len(back) == len(orig)
        for a, b in zip(orig, back):
            _assert_codec_tree(a, b, codec, global_absmax)
        return
    if hasattr(orig, "shape") and getattr(getattr(orig, "dtype", None), "kind", "") == "f":
        got = np.asarray(back)
        assert got.shape == np.asarray(orig).shape, (codec, got.shape)
        assert got.dtype == np.asarray(orig).dtype or codec == "int8", (
            codec, got.dtype,
        )
        x = np.asarray(orig)
        absmax = float(np.abs(x).max()) if x.size else 0.0
        if codec == "int8":
            # per-tensor symmetric quantization: one step of the leaf's scale
            np.testing.assert_allclose(got, x, atol=absmax / 127.0 + 1e-7)
        elif codec.startswith("int8_blocks"):
            # blocks span leaf boundaries in the fused flat buffer, so the
            # bound is one step of the worst block scale — at most the
            # payload-global absmax
            np.testing.assert_allclose(
                got, x, atol=global_absmax / 127.0 + 1e-7
            )
        elif codec.startswith("topk"):
            # sparsified: a subset of the dense magnitudes, rest zero
            assert float(np.abs(got).max(initial=0.0)) <= absmax + 1e-6
        else:
            assert got.tobytes() == x.tobytes()
        return
    if hasattr(orig, "shape") or isinstance(orig, np.generic):
        assert np.asarray(back).tobytes() == np.asarray(orig).tobytes()
        assert np.asarray(back).dtype == np.asarray(orig).dtype
        return
    assert back == orig and type(back) is type(orig), (codec, orig, back)


def check_codec_roundtrip(codec_name: str) -> None:
    """One registered codec over every fixture: encode_payload -> wire
    encode/decode -> decode_payload must preserve structure and bound the
    loss; the codec's own sentinel collisions must round-trip exactly."""
    from repro.transport.wire import (
        decode,
        decode_payload,
        encode,
        encode_payload,
        make_codec,
    )

    codec = make_codec(codec_name)
    link = ("conf-ch", "default", "a-0", "b-0")
    for fixture in _codec_fixtures():
        coded = encode_payload(fixture, codec, link=link)
        back = decode_payload(decode(encode(coded)))  # across a real buffer
        _assert_codec_tree(fixture, back, codec_name, _float_absmax(fixture))
    for prefix, fixtures in CODEC_SENTINEL_FIXTURES.items():
        if not codec_name.startswith(prefix):
            continue
        for sentinel in fixtures:
            # the escape guarantees *structure*: the colliding dict is never
            # misdecoded into a quantized/sparse leaf. Float-array members
            # are still subject to the codec's (lossy) leaf transform, like
            # any other leaf — checked via the loss envelope.
            payload = {"blob": sentinel, "n": 1}
            back = decode_payload(decode(encode(encode_payload(payload, codec, link=link))))
            assert back["n"] == 1
            _assert_codec_tree(
                sentinel, back["blob"], codec_name, _float_absmax(sentinel)
            )


# ------------------------------------------------------------------ #
# exactly-once session semantics (connection-oriented backends)
# ------------------------------------------------------------------ #
# These three checks exercise the session/replay layer through the chaos
# hooks a connection-oriented backend exposes (``_chaos_break_conn`` /
# ``_chaos_duplicate`` / ``_chaos_probe_evicted``). A backend without a
# connection to lose (inproc, the emu backends) has nothing to conform to
# here — the hooks are probed with ``getattr`` and the check passes
# vacuously, same as the optional-capability checks above.

def check_session_resume_mid_recv(factory: Factory) -> None:
    """Severing every connection under a *blocked* recv must not lose it:
    the client reconnects, resumes its session, re-attaches to the
    in-flight recv and receives the message sent after the break."""
    be = factory()
    if getattr(be, "_chaos_break_conn", None) is None:
        return
    _pair(be)
    box: Dict[str, object] = {}

    def _blocked() -> None:
        try:
            box["got"] = be.recv(CH, G, "b-0", "a-0", 30.0)
        except BaseException as exc:  # noqa: BLE001 - surfaced via assert
            box["err"] = exc

    t = threading.Thread(target=_blocked, daemon=True)
    t.start()
    time.sleep(0.2)  # let the recv frame reach the hub and block there
    be._chaos_break_conn()
    time.sleep(0.1)
    be.send(CH, G, "a-0", "b-0", {"y": 2})
    be.now("a-0")  # ack barrier: the pipelined send is confirmed delivered
    t.join(10.0)
    assert not t.is_alive(), "blocked recv did not re-attach after the break"
    assert "err" not in box, f"re-attached recv raised: {box['err']!r}"
    assert box["got"]["y"] == 2  # type: ignore[index]
    be.close()


def check_duplicate_send_dedup(factory: Factory) -> None:
    """A retransmitted (duplicate) send frame must be answered from the
    replay cache, not re-executed: exactly one copy of the message exists."""
    from repro.transport.wire import encode_payload

    be = factory()
    if getattr(be, "_chaos_duplicate", None) is None:
        return
    _pair(be)
    _, dup_status, _ = be._chaos_duplicate(
        "send", CH, G, "a-0", "b-0", encode_payload({"x": 1}, "")
    )
    assert dup_status == "ok", f"duplicate send rejected: {dup_status!r}"
    got = be.recv(CH, G, "b-0", "a-0", 5.0)
    assert got["x"] == 1
    try:
        extra = be.recv(CH, G, "b-0", "a-0", 0.2)
    except queue.Empty:
        extra = None
    assert extra is None, f"duplicate send was re-executed: {extra!r}"
    be.close()


def check_replay_window_eviction(factory: Factory) -> None:
    """A duplicate whose ack was already consumed (below the client's
    floor) must be *rejected* — replaying it could otherwise re-execute an
    op whose reply left the cache."""
    be = factory()
    if getattr(be, "_chaos_probe_evicted", None) is None:
        return
    _pair(be)
    # two completed sync ops: the second frame's floor evicts the first's
    # cached reply hub-side
    be.now("a-0")
    be.now("a-0")
    status, value = be._chaos_probe_evicted()
    assert status == "err", "evicted duplicate was answered (possibly re-run)"
    assert "replay window" in str(value), value
    be.close()


CONFORMANCE_CHECKS: Dict[str, Callable[[Factory], None]] = {
    "protocol_surface": check_protocol_surface,
    "send_recv_roundtrip": check_send_recv_roundtrip,
    "membership": check_membership,
    "fifo_order": check_fifo_order,
    "peek_nonblocking": check_peek_nonblocking,
    "earliest_empty_ends": check_earliest_empty_ends,
    "recv_timeout_empty": check_recv_timeout_empty,
    "recv_any_picks_earliest": check_recv_any_picks_earliest,
    "poison_wakes_blocked_recv": check_poison_wakes_blocked_recv,
    "poison_wakes_recv_any_multi": check_poison_wakes_recv_any_multi,
    "dropout_mid_recv_fifo": check_dropout_mid_recv_fifo,
    "dropout_on_send": check_dropout_on_send,
    "supervisor_rejoin_reset": check_supervisor_rejoin_reset,
    "clock_ops": check_clock_ops,
    "stats_accounting": check_stats_accounting,
    "send_many_delivery": check_send_many_delivery,
    "send_many_fifo_interleave": check_send_many_fifo_interleave,
    "send_many_accounting": check_send_many_accounting,
    "send_many_stateful_fallback": check_send_many_stateful_fallback,
    "install_reduce_fold": check_install_reduce_fold,
    "install_reduce_sharded": check_install_reduce_sharded,
    "session_resume_mid_recv": check_session_resume_mid_recv,
    "duplicate_send_dedup": check_duplicate_send_dedup,
    "replay_window_eviction": check_replay_window_eviction,
}


def run_conformance(
    factory: Factory, checks: Optional[Sequence[str]] = None
) -> List[str]:
    """Run (a subset of) the suite against ``factory``; returns check names
    run. Raises on first violation."""
    names = list(checks) if checks is not None else sorted(CONFORMANCE_CHECKS)
    for name in names:
        CONFORMANCE_CHECKS[name](factory)
    return names


# ------------------------------------------------------------------ #
# reference workload for cross-backend equivalence
# ------------------------------------------------------------------ #
class SeededSGDTrainer(Trainer):
    """Deterministic softmax-regression trainer for transport equivalence.

    Pure numpy, seeded by the worker's dataset name — a seeded sync FedAvg
    job built on it must produce *byte-identical* global weights on every
    transport backend. Lives in the library (not the test tree) so spawned
    worker processes can import it.
    """

    def load_data(self) -> None:
        from repro.data.datasets import synthetic_classification

        d = synthetic_classification(self.ctx.worker.dataset or "d0")
        self.x, self.y = d.x, d.y
        self.num_samples = d.num_samples

    def train(self) -> None:
        if self.weights is None:
            return
        w = np.asarray(self.weights["w"], np.float32).copy()
        b = np.asarray(self.weights["b"], np.float32).copy()
        z = self.x @ w + b
        z = z - z.max(axis=1, keepdims=True)
        e = np.exp(z)
        p = e / e.sum(axis=1, keepdims=True)
        onehot = np.eye(w.shape[1], dtype=np.float32)[self.y]
        g = (p - onehot) / np.float32(self.x.shape[0])
        w -= np.float32(0.2) * (self.x.T @ g)
        b -= np.float32(0.2) * g.sum(axis=0)
        self.weights = {"w": w, "b": b}
