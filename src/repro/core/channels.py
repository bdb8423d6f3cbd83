"""Channel API and communication backends (§4.1 "Channel", Table 2).

The channel manager gives every role a uniform messaging surface —
``join/leave/send/recv/recv_fifo/peek/broadcast/ends/empty`` — regardless of
the underlying backend. The backend itself is a first-class, swappable
abstraction: anything implementing the ``TransportBackend`` protocol can sit
behind a ``ChannelEnd``. Backends registered here:

* ``inproc``   — thread-safe in-process queues. This is the emulation backend
  (Flame-in-a-box analogue) used by the paper-experiment reproductions; it
  supports a per-link *bandwidth/latency model* so §6.1/§6.2 straggler and
  backend-selection experiments are measurable.
* ``mqtt-emu`` — inproc with a broker contention model: traffic to the same
  topic (one receiver's subscription on a channel/group) serializes on the
  broker, while distinct topics proceed in parallel (models the paper's
  "MQTT traffic over WAN via a broker" inefficiency per topic).
* ``p2p-emu``  — inproc with per-link bandwidth (direct peering).
* ``collective`` — not a message queue at all: marks the channel as lowered to
  jax.lax collectives on the TPU mesh (see ``repro.core.mesh_lowering``).

A real multi-process transport (each worker an OS process, messages over
sockets) lives in ``repro.transport.multiproc``; it implements the same
protocol and is driven through the same ``ChannelManager``/``ChannelEnd``
surface — deployment choice, not application logic (§6.2).

Payloads are pytrees; wire cost is computed from leaf sizes after the
channel's ``wire_dtype`` / compression policy, so bandwidth emulation and the
roofline collective term share one accounting path (``payload_bytes``).
"""
from __future__ import annotations

import collections
import dataclasses
import os
import queue
import threading
import time
from typing import (
    Any,
    Callable,
    Dict,
    Iterable,
    List,
    Optional,
    Protocol,
    Sequence,
    Tuple,
)

import numpy as np

from repro.core.spans import RECV, SEND, role_span, span
from repro.core.tag import Channel as ChannelSpec

_WIRE_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "int8": 1}


def payload_bytes(payload: Any, wire_dtype: str = "f32") -> int:
    """Bytes of a pytree payload on the wire under ``wire_dtype``.

    ``wire_dtype`` caps the per-element width: a leaf already narrower than
    the wire dtype (int8 quantized blocks, int32 top-k indices) is counted
    at its own element size — a coded payload's accounting reflects the
    bytes it actually moves instead of inflating every element to the
    channel's float width."""
    import jax

    per = _WIRE_BYTES.get(wire_dtype, 4)
    leaves = jax.tree_util.tree_leaves(payload)
    total = 0
    for leaf in leaves:
        size = np.size(leaf) if hasattr(leaf, "shape") or np.ndim(leaf) else 1
        itemsize = getattr(getattr(leaf, "dtype", None), "itemsize", per)
        total += int(size) * min(per, int(itemsize))
    return total


@dataclasses.dataclass
class LinkModel:
    """Emulated link characteristics for an end (bandwidth in bytes/sec)."""

    bandwidth: float = float("inf")
    latency: float = 0.0

    def transfer_time(self, nbytes: int) -> float:
        bw = self.bandwidth if self.bandwidth > 0 else float("inf")
        return self.latency + (nbytes / bw if bw != float("inf") else 0.0)


@dataclasses.dataclass
class Message:
    src: str
    payload: Any
    nbytes: int
    arrival: float  # emulated arrival time (seconds on the virtual clock)


class WorkerDropped(RuntimeError):
    """Raised from a channel operation when the worker's virtual clock would
    cross its scheduled dropout time (mid-round dropout emulation)."""

    def __init__(self, worker: str, at: float) -> None:
        super().__init__(f"worker {worker!r} dropped out at t={at:.3f}s (virtual)")
        self.worker = worker
        self.at = at


# Every operation a transport must provide. The conformance suite
# (``repro.transport.conformance``) checks both presence and semantics of
# these ops for every registered backend.
TRANSPORT_OPS: Tuple[str, ...] = (
    # membership
    "join", "leave", "peers",
    # messaging
    "send", "send_many", "recv", "recv_any", "recv_fifo", "peek", "earliest",
    # failure emulation / cancellation
    "set_drop", "clear_drop", "drop_time", "poison", "check_poison",
    # link / wire configuration
    "set_link", "set_wire_dtype", "link",
    # clocks
    "now", "advance", "set_clock",
    # reduce plane (hub-side partial aggregation of an incast topic)
    "install_reduce",
)


class TransportBackend(Protocol):
    """The pluggable transport contract behind ``ChannelEnd``.

    ``InprocBackend`` (threads + queues, virtual clock) is the reference
    implementation; ``repro.transport.multiproc.MultiprocBackend`` speaks the
    same protocol over sockets to a broker in the driver process. ``ChannelEnd``,
    ``recv_any_multi``, the backend registry and ``ChannelManager`` depend only
    on this protocol — never on a concrete class.

    Semantics every implementation must honor (enforced by the shared
    conformance suite):

    * per-``(channel, group, dst, src)`` FIFO mailboxes;
    * ``recv``/``recv_any`` block (wall-clock) until delivery, ``queue.Empty``
      on timeout;
    * ``poison(worker)`` wakes any blocked receive of ``worker`` immediately
      with ``WorkerDropped``;
    * clock ops (``now``/``advance``/``set_clock``) keep a monotone per-worker
      time in seconds, and any operation carrying a worker's clock past its
      ``set_drop`` time raises ``WorkerDropped``.
    """

    name: str
    stats: Dict[str, float]

    # --------------------------- membership --------------------------- #
    def join(self, channel: str, group: str, worker: str) -> None: ...
    def leave(self, channel: str, group: str, worker: str) -> None: ...
    def peers(self, channel: str, group: str, me: str) -> List[str]: ...

    # ---------------------------- messaging --------------------------- #
    def send(self, channel: str, group: str, src: str, dst: str, payload: Any) -> None: ...
    def send_many(
        self, channel: str, group: str, src: str, dsts: Sequence[str], payload: Any
    ) -> None: ...
    def recv(
        self, channel: str, group: str, me: str, end: str, timeout: Optional[float]
    ) -> Any: ...
    def recv_any(
        self,
        channel: str,
        group: str,
        me: str,
        ends: Sequence[str],
        timeout: Optional[float],
        advance: bool = True,
    ) -> Tuple[str, Any, float]: ...
    def recv_fifo(
        self,
        channel: str,
        group: str,
        me: str,
        ends: Sequence[str],
        timeout: Optional[float],
    ) -> Iterable[Tuple[str, Any]]: ...
    def peek(self, channel: str, group: str, me: str, end: str) -> Optional[Any]: ...
    def earliest(
        self, channel: str, group: str, me: str, ends: Sequence[str]
    ) -> Optional[Tuple[float, str]]: ...

    # ------------------- failure emulation / cancel -------------------- #
    def set_drop(self, worker: str, at: float) -> None: ...
    def clear_drop(self, worker: str) -> None: ...
    def drop_time(self, worker: str) -> Optional[float]: ...
    def poison(self, worker: str, at: float) -> None: ...
    def check_poison(self, worker: str) -> None: ...

    # ------------------------- configuration -------------------------- #
    def set_link(self, channel: str, worker: str, model: LinkModel) -> None: ...
    def set_wire_dtype(self, channel: str, dtype: str) -> None: ...
    def link(self, channel: str, worker: str) -> LinkModel: ...

    # ----------------------------- clocks ------------------------------ #
    def now(self, worker: str) -> float: ...
    def advance(self, worker: str, seconds: float) -> None: ...
    def set_clock(self, worker: str, at: float) -> None: ...

    # --------------------------- reduce plane -------------------------- #
    def install_reduce(
        self,
        channel: str,
        group: str,
        dst: str,
        srcs: Sequence[str],
        shards: int = 1,
        fused: Optional[bool] = None,
    ) -> None: ...


# Broadcast fan-out fast path: when enabled (the default), ChannelEnd lowers
# multi-destination sends onto the backend's ``send_many`` op — one encode /
# one RPC per logical broadcast instead of one per destination. The env var
# reaches spawned worker processes (spawn children inherit os.environ), so a
# single toggle flips every deployment; byte accounting is bit-identical
# either way, which the equivalence tests pin.
_FANOUT_ENABLED = os.environ.get("REPRO_BROADCAST_FANOUT", "1") not in ("0", "false")


def set_broadcast_fanout(enabled: bool) -> None:
    """Enable/disable the ``send_many`` broadcast fast path process-wide."""
    global _FANOUT_ENABLED
    _FANOUT_ENABLED = bool(enabled)


def broadcast_fanout_enabled() -> bool:
    return _FANOUT_ENABLED


# Hub-reduce kill switch: the reduce plane is opt-in per job (``reduce_plan``
# hyperparam), but this process-wide toggle can veto it everywhere — the
# uplink mirror of REPRO_BROADCAST_FANOUT. Spawned workers inherit the env
# var, so one setting governs every deployment of a job.
_HUB_REDUCE_ENABLED = os.environ.get("REPRO_HUB_REDUCE", "1") not in ("0", "false")


def set_hub_reduce(enabled: bool) -> None:
    """Enable/disable hub-side partial aggregation process-wide."""
    global _HUB_REDUCE_ENABLED
    _HUB_REDUCE_ENABLED = bool(enabled)


def hub_reduce_enabled() -> bool:
    return _HUB_REDUCE_ENABLED


def reduce_blocks(srcs: Sequence[str], shards: int) -> List[List[str]]:
    """Partition an incast's sources into the reduce plan's shard blocks.

    Sorted sources, contiguous blocks, sizes as even as possible — the ONE
    partition function shared by the installing server and the reducing
    broker, so both sides agree on which pseudo-source delivers which
    partial. Returns ``[]`` when ``srcs`` is empty or ``shards < 1`` (reduce
    off)."""
    order = sorted(srcs)
    if not order or int(shards) < 1:
        return []
    n = min(int(shards), len(order))
    q, r = divmod(len(order), n)
    blocks: List[List[str]] = []
    i = 0
    for b in range(n):
        size = q + (1 if b < r else 0)
        blocks.append(order[i:i + size])
        i += size
    return blocks


# Decode pool for the per-frame incast path: the receiving end fetches (and,
# on socket transports, wire-decodes) frames from several sources
# concurrently, while the aggregation fold still consumes them in sorted-src
# order — parallel decode, unchanged fold order, so results stay
# bit-identical to the sequential loop. 0 or 1 disables pooling.
_DECODE_POOL_WORKERS = int(os.environ.get("REPRO_DECODE_POOL", "4") or 0)
_DECODE_POOL = None
_DECODE_POOL_SIZE = 0
_DECODE_POOL_LOCK = threading.Lock()


def set_decode_pool(workers: int) -> None:
    """Set the receive-side decode concurrency (0/1 = sequential)."""
    global _DECODE_POOL_WORKERS
    _DECODE_POOL_WORKERS = max(0, int(workers))


def decode_pool_workers() -> int:
    return _DECODE_POOL_WORKERS


def _decode_pool(workers: int):
    """Shared lazily-built executor; grows if a larger pool is requested.

    One process-wide pool: its threads acquire per-backend thread-local
    sockets on first use, so concurrent fetches from a transport hub ride
    separate connections and genuinely overlap decode work."""
    global _DECODE_POOL, _DECODE_POOL_SIZE
    from concurrent.futures import ThreadPoolExecutor

    with _DECODE_POOL_LOCK:
        if _DECODE_POOL is None or _DECODE_POOL_SIZE < workers:
            if _DECODE_POOL is not None:
                _DECODE_POOL.shutdown(wait=False)
            _DECODE_POOL = ThreadPoolExecutor(
                max_workers=workers, thread_name_prefix="decode-pool"
            )
            _DECODE_POOL_SIZE = workers
        return _DECODE_POOL


class ChannelEnd:
    """One worker's handle on a channel — implements Table 2.

    ``peer_role`` (when set) restricts ``ends()`` to workers of the role at
    the other end of the channel, so a group shared by several roles (e.g.
    aggregators + global aggregator on one channel) still resolves peers
    unambiguously. ``ends()`` is also the hook for the paper's "chosen peer
    selection logic" (Table 2) via ``peer_selector``.
    """

    def __init__(
        self,
        backend: TransportBackend,
        channel: str,
        group: str,
        me: str,
        peer_role: Optional[str] = None,
        peer_selector: Optional[Callable[[List[str]], List[str]]] = None,
    ):
        self._backend = backend
        self.channel = channel
        self.group = group
        self.me = me
        self.peer_role = peer_role
        self.peer_selector = peer_selector
        self._joined = False
        role = me.rsplit("-", 1)[0]
        self._recv_span = role_span(role, RECV)
        self._send_span = role_span(role, SEND)

    # ----------------------------- lifecycle -------------------------- #
    def join(self) -> None:
        self._backend.join(self.channel, self.group, self.me)
        self._joined = True

    def leave(self) -> None:
        self._backend.leave(self.channel, self.group, self.me)
        self._joined = False

    # ----------------------------- messaging -------------------------- #
    def send(self, end: str, msg: Any) -> None:
        with span(self._send_span):
            self._backend.send(self.channel, self.group, self.me, end, msg)

    def recv(self, end: str, timeout: Optional[float] = 30.0) -> Any:
        with span(self._recv_span):
            return self._backend.recv(self.channel, self.group, self.me, end, timeout)

    def recv_fifo(self, ends: Sequence[str], timeout: Optional[float] = 30.0):
        """Yield (end, message) for each end, in arrival (FIFO) order."""
        return self._backend.recv_fifo(self.channel, self.group, self.me, ends, timeout)

    def recv_any(
        self,
        ends: Sequence[str],
        timeout: Optional[float] = 30.0,
        advance: bool = True,
    ) -> Tuple[str, Any, float]:
        """Earliest available message from any of ``ends``:
        ``(end, payload, virtual_arrival)``. Raises ``queue.Empty`` on
        timeout — the async servers' reactive receive."""
        return self._backend.recv_any(
            self.channel, self.group, self.me, ends, timeout, advance=advance
        )

    def peek(self, end: str) -> Optional[Any]:
        return self._backend.peek(self.channel, self.group, self.me, end)

    def earliest(self, ends: Sequence[str]) -> Optional[Tuple[float, str]]:
        """Non-consuming ``(arrival, end)`` of the earliest available message
        from any of ``ends`` on this channel, or ``None``."""
        return self._backend.earliest(self.channel, self.group, self.me, ends)

    def send_many(self, ends: Sequence[str], msg: Any) -> None:
        """Send one payload to several destinations.

        Lowers onto the backend's ``send_many`` (one encode, one RPC, broker-
        side fan-out) when the fast path is enabled; otherwise loops ``send``.
        Ordering, virtual-clock arithmetic and byte accounting are identical
        to the per-destination loop in both modes."""
        if not ends:
            return
        if _FANOUT_ENABLED and len(ends) > 1:
            with span(self._send_span):
                self._backend.send_many(
                    self.channel, self.group, self.me, list(ends), msg
                )
        else:
            for end in ends:
                self.send(end, msg)

    def broadcast(self, msg: Any) -> None:
        self.send_many(self.ends(), msg)

    # --------------------------- reduce plane -------------------------- #
    def install_reduce(
        self, srcs: Sequence[str], shards: int = 1, fused: Optional[bool] = None
    ) -> None:
        """Install (or, with empty ``srcs``/``shards < 1``, remove) a
        hub-side reduce spec for this end's incast.

        While installed, the broker folds arriving update frames from
        ``srcs`` into per-shard ``(partial_sum, total_weight, srcs)``
        accumulators and this end receives ONE partial frame per shard —
        from the pseudo-sources ``wire.reduce_src(i)`` — instead of one
        frame per source. Client-side ``bytes:``/``msgs:`` accounting is
        untouched; the folded frames surface in ``hub_reduced:`` /
        ``hub_partials:`` counters."""
        self._backend.install_reduce(
            self.channel, self.group, self.me, list(srcs), int(shards), fused
        )

    def recv_ordered(self, ends: Sequence[str], timeout: Optional[float] = 30.0):
        """Receive one message from each of ``ends``, yielding
        ``(end, payload)`` in sorted-``ends`` order.

        With the decode pool enabled, the per-source fetches run
        concurrently (each pool thread rides its own hub connection on
        socket transports, so wire decode genuinely overlaps) while
        consumption stays strictly sorted — the fold order, clock effects
        and failure surfacing are identical to the sequential
        ``for end in sorted(ends): recv(end)`` loop, so aggregation results
        remain bit-identical to it. In-flight decoded frames are bounded by
        the pool size, preserving the server's O(1)-in-group-size memory up
        to that constant."""
        order = sorted(ends)
        workers = decode_pool_workers()
        if workers <= 1 or len(order) <= 1:
            for end in order:
                yield end, self.recv(end, timeout=timeout)
            return
        pool = _decode_pool(workers)
        futs = [
            pool.submit(
                self._backend.recv, self.channel, self.group, self.me, end, timeout
            )
            for end in order
        ]
        for end, fut in zip(order, futs):
            # the span closes before the yield: the consumer's fold is not
            # the wait for this frame
            with span(self._recv_span):
                msg = fut.result()
            yield end, msg

    # ----------------------------- topology --------------------------- #
    def ends(self) -> List[str]:
        peers = self._backend.peers(self.channel, self.group, self.me)
        if self.peer_role is not None:
            peers = [p for p in peers if p.rsplit("-", 1)[0] == self.peer_role]
        if self.peer_selector is not None:
            peers = self.peer_selector(peers)
        return peers

    def empty(self) -> bool:
        return not self.ends()

    # ------------------- clocks / failure emulation -------------------- #
    # Role bodies reach the backend only through ChannelEnd; these wrappers
    # cover the clock and cancellation surface so no role needs a concrete
    # backend handle (the driver/worker split of the multiproc transport).
    def now(self) -> float:
        return self._backend.now(self.me)

    def advance(self, seconds: float) -> None:
        self._backend.advance(self.me, seconds)

    def set_clock(self, at: float) -> None:
        self._backend.set_clock(self.me, at)

    def check_poison(self) -> None:
        self._backend.check_poison(self.me)

    def drop_time(self, worker: Optional[str] = None) -> Optional[float]:
        return self._backend.drop_time(worker if worker is not None else self.me)


class _ReduceState:
    """Broker-side partial-aggregation state for one reduced incast topic.

    ``blocks`` is the shard partition from :func:`reduce_blocks`. Arriving
    updates are held in ``pending`` until they can be folded in sorted-src
    order (a cursor per block), so the fold order — and therefore the shard
    partial's bit pattern — is independent of arrival order. Out-of-order
    buffering is bounded by the block size, never worse than the unreduced
    mailbox backlog. When a block's cursor completes, one partial frame is
    emitted and the block resets for the next round."""

    def __init__(self, blocks: List[List[str]], fused: Optional[bool]) -> None:
        self.blocks = blocks
        self.fused = fused
        self.block_of: Dict[str, int] = {
            s: i for i, b in enumerate(blocks) for s in b
        }
        self.pending: List[Dict[str, Tuple[Any, float]]] = [{} for _ in blocks]
        self.cursor: List[int] = [0] * len(blocks)
        self.acc: List[Any] = [None] * len(blocks)
        self.hwm: List[float] = [0.0] * len(blocks)  # latest folded arrival


class InprocBackend:
    """Thread-safe in-process message transport with an emulated clock.

    The reference ``TransportBackend`` implementation. Every (channel, group)
    is a mailbox keyed by (dst, src). Virtual time advances by each message's
    modeled transfer duration; ``recv`` blocks the receiving thread until real
    delivery, while ``delivered_at`` records the *emulated* completion time
    used by the paper-experiment harnesses.

    ``wall_clock=True`` maps real elapsed time onto the same clock API: a
    worker's clock never falls behind the wall-clock seconds since backend
    creation, so transfer modeling, dropout schedules and arrival ordering
    keep working when the backend serves real OS processes (the multiproc
    transport hub wraps an instance in this mode).
    """

    def __init__(
        self,
        name: str = "inproc",
        shared_broker: bool = False,
        wall_clock: bool = False,
    ):
        self.name = name
        self.shared_broker = shared_broker
        self.wall_clock = wall_clock
        self._t0 = time.monotonic()
        self._lock = threading.RLock()
        self._cv = threading.Condition(self._lock)  # signaled on every delivery
        self._members: Dict[Tuple[str, str], List[str]] = collections.defaultdict(list)
        self._boxes: Dict[Tuple[str, str, str, str], "queue.Queue[Message]"] = {}
        self._links: Dict[Tuple[str, str], LinkModel] = {}
        self._wire_dtype: Dict[str, str] = {}
        # channel -> codec object used for *accounting only*: emulated
        # payloads never leave the process, but a coded channel's transfer
        # time and byte stats must reflect post-codec wire bytes
        self._codec_acct: Dict[str, Any] = {}
        # broker contention is per *topic* — one receiver's subscription on a
        # (channel, group): transfers to the same receiver serialize on the
        # broker uplink, distinct topics proceed in parallel (§6.2)
        self._broker_free_at: Dict[Tuple[str, str, str], float] = collections.defaultdict(
            float
        )
        self._clock: Dict[str, float] = collections.defaultdict(float)  # per-worker
        # reduce plane: (channel, group, dst) -> broker-side fold state
        self._reduce: Dict[Tuple[str, str, str], _ReduceState] = {}
        self._drop_at: Dict[str, float] = {}  # worker -> scheduled dropout time
        self._poisoned: Dict[str, float] = {}  # worker -> orphaned-at time
        self.stats: Dict[str, float] = collections.defaultdict(float)

    def _wall(self) -> float:
        return time.monotonic() - self._t0

    # ------------------------- configuration -------------------------- #
    def set_link(self, channel: str, worker: str, model: LinkModel) -> None:
        self._links[(channel, worker)] = model

    def set_wire_dtype(self, channel: str, dtype: str) -> None:
        self._wire_dtype[channel] = dtype

    def set_codec(self, channel: str, codec: str) -> None:
        """Account ``channel``'s emulated wire bytes post-codec.

        Emulation payloads never actually cross a socket, so the codec is
        never *run* here — but a coded channel's emulated ``transfer_time``
        and ``stats["bytes:..."]`` must not overstate wire bytes by the
        compression ratio. The raw size is kept in ``raw_bytes:<channel>``
        so the achieved ratio is observable per channel."""
        from repro.transport.wire import make_codec

        if codec:
            self._codec_acct[channel] = make_codec(codec)
        else:
            self._codec_acct.pop(channel, None)

    def link(self, channel: str, worker: str) -> LinkModel:
        return self._links.get((channel, worker), LinkModel())

    # --------------------------- dropout ------------------------------ #
    def set_drop(self, worker: str, at: float) -> None:
        """Schedule ``worker`` to drop out once its virtual clock crosses
        ``at``. Enforced by every clock-advancing channel operation."""
        with self._lock:
            self._drop_at[worker] = float(at)

    def clear_drop(self, worker: str) -> None:
        with self._lock:
            self._drop_at.pop(worker, None)
            self._poisoned.pop(worker, None)

    def drop_time(self, worker: str) -> Optional[float]:
        with self._lock:
            return self._drop_at.get(worker)

    def poison(self, worker: str, at: float) -> None:
        """Mark ``worker`` as orphaned at virtual time ``at`` (its sole
        upstream peer died with no re-join scheduled). Any blocked or future
        receive by the worker raises ``WorkerDropped`` immediately, so the
        orphan is surfaced instead of hanging until its recv timeout."""
        with self._cv:
            self._poisoned[worker] = float(at)
            self._cv.notify_all()

    def check_poison(self, worker: str) -> None:
        """Raise ``WorkerDropped`` if ``worker`` has been poisoned."""
        with self._lock:
            at = self._poisoned.get(worker)
        if at is not None:
            raise WorkerDropped(worker, at)

    def _check_poison_locked(self, worker: str) -> None:
        at = self._poisoned.get(worker)
        if at is not None:
            raise WorkerDropped(worker, at)

    def _check_alive(self, worker: str, new_time: float) -> None:
        """Raise WorkerDropped if moving ``worker``'s clock to ``new_time``
        crosses its dropout time. Caller must hold the lock."""
        at = self._drop_at.get(worker)
        if at is not None and new_time > at:
            self._clock[worker] = max(self._clock[worker], at)
            raise WorkerDropped(worker, at)

    # --------------------------- membership --------------------------- #
    def join(self, channel: str, group: str, worker: str) -> None:
        with self._lock:
            members = self._members[(channel, group)]
            if worker not in members:
                members.append(worker)

    def leave(self, channel: str, group: str, worker: str) -> None:
        with self._lock:
            members = self._members[(channel, group)]
            if worker in members:
                members.remove(worker)

    def peers(self, channel: str, group: str, me: str) -> List[str]:
        with self._lock:
            return [m for m in self._members[(channel, group)] if m != me]

    # --------------------------- reduce plane -------------------------- #
    def install_reduce(
        self,
        channel: str,
        group: str,
        dst: str,
        srcs: Sequence[str],
        shards: int = 1,
        fused: Optional[bool] = None,
    ) -> None:
        """Install/replace (or remove) the reduce spec for one incast topic.

        An absolute-state write like ``set_link``: installing resets the
        topic's accumulator state for a fresh round; empty ``srcs`` or
        ``shards < 1`` uninstalls and restores per-frame delivery. The
        installing server must issue this *before* the round's uploads can
        be triggered (in practice: before its broadcast), so no update frame
        races the spec."""
        key = (channel, group, dst)
        blocks = reduce_blocks(srcs, shards)
        with self._lock:
            if not blocks:
                self._reduce.pop(key, None)
            else:
                self._reduce[key] = _ReduceState(blocks, fused)

    def _reduce_ingest(
        self,
        channel: str,
        group: str,
        dst: str,
        state: _ReduceState,
        src: str,
        payload: Any,
        arrival: float,
    ) -> bool:
        """Fold one arriving update frame broker-side. Caller holds the lock.

        Returns True when the frame was absorbed by the reduce plane (no
        per-frame delivery); False lets the caller deliver it normally — a
        frame that is not a weight-sync update (no ``weights`` field after
        codec decode) must never be silently swallowed."""
        from repro.transport.wire import decode_payload, pack_hub_partial, reduce_src

        decoded = decode_payload(payload)
        if not isinstance(decoded, dict) or "weights" not in decoded:
            return False
        i = state.block_of[src]
        state.pending[i][src] = (decoded, arrival)
        self.stats[f"hub_reduced:{channel}"] += 1
        block = state.blocks[i]
        cur = state.cursor[i]
        while cur < len(block) and block[cur] in state.pending[i]:
            upd, arr = state.pending[i].pop(block[cur])
            if state.acc[i] is None:
                from repro.core.roles import StreamingMean

                state.acc[i] = StreamingMean(fused=state.fused)
            state.acc[i].fold(upd["weights"], float(upd.get("num_samples", 1)))
            state.hwm[i] = max(state.hwm[i], arr)
            cur += 1
        state.cursor[i] = cur
        if cur == len(block):
            acc_tree, total = state.acc[i].partial()
            part = pack_hub_partial(
                i, block, acc_tree, total, state.acc[i].count
            )
            wire = self._wire_dtype.get(channel, "f32")
            self._box(channel, group, dst, reduce_src(i)).put(
                Message(
                    reduce_src(i), part, payload_bytes(acc_tree, wire),
                    state.hwm[i],
                )
            )
            self.stats[f"hub_partials:{channel}"] += 1
            # reset the block for the next round (the spec stays installed)
            state.acc[i] = None
            state.cursor[i] = 0
            state.hwm[i] = 0.0
        return True

    # ---------------------------- transport ---------------------------- #
    def _box(self, channel: str, group: str, dst: str, src: str) -> "queue.Queue[Message]":
        key = (channel, group, dst, src)
        with self._lock:
            if key not in self._boxes:
                self._boxes[key] = queue.Queue()
            return self._boxes[key]

    def send(self, channel: str, group: str, src: str, dst: str, payload: Any) -> None:
        wire = self._wire_dtype.get(channel, "f32")
        codec = self._codec_acct.get(channel)
        raw_bytes = payload_bytes(payload, wire)
        if codec is None:
            nbytes = raw_bytes
        else:
            # post-codec accounting: the emulated transfer moves what the
            # codec would put on a real wire, not the raw float payload
            nbytes = codec.wire_bytes(payload, wire)
        sender_link = self.link(channel, src)
        dur = sender_link.transfer_time(nbytes)
        topic = (channel, group, dst)
        with self._lock:
            start = self._clock[src]
            if self.wall_clock:
                start = max(start, self._wall())
            if self.shared_broker:
                # broker serializes transfers on the destination's topic only
                start = max(start, self._broker_free_at[topic])
            arrival = start + dur
            drop_at = self._drop_at.get(src)
            if drop_at is not None and arrival > drop_at:
                # sender dies mid-transfer: nothing is delivered, and on a
                # shared broker the aborted transfer occupies the topic
                # only until the moment of death
                if self.shared_broker:
                    self._broker_free_at[topic] = max(
                        self._broker_free_at[topic], min(drop_at, start + dur)
                    )
                self._check_alive(src, arrival)  # raises WorkerDropped
            if self.shared_broker:
                self._broker_free_at[topic] = start + dur
            self._clock[src] = arrival
            self.stats[f"bytes:{channel}"] += nbytes
            self.stats[f"msgs:{channel}"] += 1
            if codec is not None:
                self.stats[f"raw_bytes:{channel}"] += raw_bytes
            state = self._reduce.get(topic)
            if not (
                state is not None
                and src in state.block_of
                and self._reduce_ingest(
                    channel, group, dst, state, src, payload, arrival
                )
            ):
                self._box(channel, group, dst, src).put(
                    Message(src, payload, nbytes, arrival)
                )
            self._cv.notify_all()

    def send_many(
        self, channel: str, group: str, src: str, dsts: Sequence[str], payload: Any
    ) -> None:
        """Deliver one payload to every dst — O(1) encode/accounting work.

        Payload sizing (``payload_bytes`` / codec accounting walk) runs once;
        the per-destination clock/broker/dropout arithmetic replicates the
        ``send`` loop exactly under a single lock hold, so arrivals, stats
        and dropout behavior are bit-identical to ``for dst: send(dst)``.
        The same payload object is delivered by reference to each mailbox,
        exactly as the loop would."""
        if not dsts:
            return
        wire = self._wire_dtype.get(channel, "f32")
        codec = self._codec_acct.get(channel)
        raw_bytes = payload_bytes(payload, wire)
        if codec is None:
            nbytes = raw_bytes
        else:
            nbytes = codec.wire_bytes(payload, wire)
        sender_link = self.link(channel, src)
        dur = sender_link.transfer_time(nbytes)
        with self._lock:
            try:
                for dst in dsts:
                    topic = (channel, group, dst)
                    start = self._clock[src]
                    if self.wall_clock:
                        start = max(start, self._wall())
                    if self.shared_broker:
                        start = max(start, self._broker_free_at[topic])
                    arrival = start + dur
                    drop_at = self._drop_at.get(src)
                    if drop_at is not None and arrival > drop_at:
                        # sender dies mid-fan-out: earlier dsts already have
                        # their copies (same as the per-dst loop), this and
                        # later transfers never complete
                        if self.shared_broker:
                            self._broker_free_at[topic] = max(
                                self._broker_free_at[topic], min(drop_at, start + dur)
                            )
                        self._check_alive(src, arrival)  # raises WorkerDropped
                    if self.shared_broker:
                        self._broker_free_at[topic] = start + dur
                    self._clock[src] = arrival
                    self.stats[f"bytes:{channel}"] += nbytes
                    self.stats[f"msgs:{channel}"] += 1
                    if codec is not None:
                        self.stats[f"raw_bytes:{channel}"] += raw_bytes
                    state = self._reduce.get(topic)
                    if not (
                        state is not None
                        and src in state.block_of
                        and self._reduce_ingest(
                            channel, group, dst, state, src, payload, arrival
                        )
                    ):
                        self._box(channel, group, dst, src).put(
                            Message(src, payload, nbytes, arrival)
                        )
            finally:
                # wake receivers even when a mid-fan-out dropout aborts the
                # loop — earlier destinations' messages are already delivered
                self._cv.notify_all()

    def _get_msg(
        self, channel: str, group: str, me: str, end: str, timeout: Optional[float]
    ) -> Message:
        """Blocking single-box take on the delivery condition variable, so a
        ``poison`` call interrupts a blocked receiver immediately. Caller must
        NOT hold the lock. Raises ``queue.Empty`` on timeout."""
        box = self._box(channel, group, me, end)
        deadline = None if timeout is None else time.monotonic() + timeout
        with self._cv:
            while True:
                self._check_poison_locked(me)
                try:
                    return box.get_nowait()
                except queue.Empty:
                    pass
                remaining = (
                    None if deadline is None else deadline - time.monotonic()
                )
                if remaining is not None and remaining <= 0:
                    raise queue.Empty
                self._cv.wait(timeout=remaining)

    def recv(
        self, channel: str, group: str, me: str, end: str, timeout: Optional[float]
    ) -> Any:
        msg = self._get_msg(channel, group, me, end, timeout)
        with self._lock:
            self._check_alive(me, msg.arrival)
            self._clock[me] = max(self._clock[me], msg.arrival)
        return msg.payload

    def recv_any(
        self,
        channel: str,
        group: str,
        me: str,
        ends: Sequence[str],
        timeout: Optional[float],
        advance: bool = True,
    ) -> Tuple[str, Any, float]:
        """Take the earliest-arriving available message from any of ``ends``.

        Returns ``(end, payload, arrival)``. Blocks (wall-clock) until a
        message is available or ``timeout`` elapses (-> ``queue.Empty``).
        This is the event-driven server primitive: async/deadline aggregators
        react to whichever worker finishes first on the virtual clock.
        ``advance=False`` leaves the receiver's virtual clock untouched (a
        deadline server closing a round must not be dragged forward by a
        straggler's late arrival).
        """
        deadline = None if timeout is None else time.monotonic() + timeout
        with self._cv:
            while True:
                self._check_poison_locked(me)
                best = self._earliest_locked(channel, group, me, ends)
                if best is not None:
                    _, end = best
                    msg = self._box(channel, group, me, end).get_nowait()
                    if advance:
                        self._check_alive(me, msg.arrival)
                        self._clock[me] = max(self._clock[me], msg.arrival)
                    return end, msg.payload, msg.arrival
                remaining = (
                    None if deadline is None else deadline - time.monotonic()
                )
                if remaining is not None and remaining <= 0:
                    raise queue.Empty
                if not self._cv.wait(timeout=remaining):
                    raise queue.Empty

    def _earliest_locked(
        self, channel: str, group: str, me: str, ends: Sequence[str]
    ) -> Optional[Tuple[float, str]]:
        best: Optional[Tuple[float, str]] = None
        for end in ends:
            box = self._box(channel, group, me, end)
            try:
                arrival = box.queue[0].arrival  # type: ignore[attr-defined]
            except IndexError:
                continue
            if best is None or arrival < best[0]:
                best = (arrival, end)
        return best

    def earliest(
        self, channel: str, group: str, me: str, ends: Sequence[str]
    ) -> Optional[Tuple[float, str]]:
        """Non-consuming query: ``(arrival, end)`` of the earliest available
        message from any of ``ends``, or ``None``. Lets a worker that listens
        on several channels (an intermediate aggregator: trainers below, the
        root above) pick the globally earliest message — see
        ``recv_any_multi``."""
        with self._lock:
            return self._earliest_locked(channel, group, me, ends)

    def recv_fifo(
        self,
        channel: str,
        group: str,
        me: str,
        ends: Sequence[str],
        timeout: Optional[float],
    ) -> Iterable[Tuple[str, Any]]:
        """Drain one message from each end, yielding in emulated-arrival order."""
        msgs: List[Tuple[float, str, Any]] = []
        for end in ends:
            m = self._get_msg(channel, group, me, end, timeout)
            msgs.append((m.arrival, end, m.payload))
        msgs.sort(key=lambda t: t[0])
        with self._lock:
            if msgs:
                self._check_alive(me, msgs[-1][0])
                self._clock[me] = max(self._clock[me], msgs[-1][0])
        for _, end, payload in msgs:
            yield end, payload

    def peek(self, channel: str, group: str, me: str, end: str) -> Optional[Any]:
        box = self._box(channel, group, me, end)
        with self._lock:
            try:
                return box.queue[0].payload  # type: ignore[attr-defined]
            except IndexError:
                return None

    # ---------------------------- clocks ------------------------------ #
    def now(self, worker: str) -> float:
        with self._lock:
            if self.wall_clock:
                t = self._wall()
                # a dropped worker's clock stays frozen at its dropout time —
                # wall time must not silently resurrect it
                drop_at = self._drop_at.get(worker)
                if drop_at is not None:
                    t = min(t, drop_at)
                self._clock[worker] = max(self._clock[worker], t)
            return self._clock[worker]

    def advance(self, worker: str, seconds: float) -> None:
        """Advance a worker's emulated clock (models local compute time)."""
        with self._lock:
            self._check_alive(worker, self._clock[worker] + seconds)
            self._clock[worker] += seconds

    def set_clock(self, worker: str, at: float) -> None:
        """Force a worker's clock forward to ``at`` (arrival / re-join)."""
        with self._lock:
            self._clock[worker] = max(self._clock[worker], float(at))

    def fabric_time(self) -> float:
        """Max across all worker clocks: the fabric's notion of "how far the
        job has progressed", used by the chaos plane to trigger seeded
        hub-level faults (``hub_crash(shard, at)``) deterministically."""
        with self._lock:
            return max(self._clock.values(), default=0.0)


def recv_any_multi(
    sources: Sequence[Tuple[ChannelEnd, Sequence[str]]],
    timeout: Optional[float] = None,
    poll: float = 0.005,
) -> Tuple[ChannelEnd, str, Any, float]:
    """Earliest available message across *several channels*.

    ``sources`` is ``[(channel_end, candidate_peers), ...]`` — typically an
    intermediate aggregator's down channel (trainer updates) and up channel
    (root broadcasts), which live on different backends and therefore cannot
    share one condition variable. Returns ``(end, src, payload, arrival)``
    for the globally earliest message, advancing the receiver's clock on the
    winning backend only (callers bridge clocks across backends themselves).

    Raises ``queue.Empty`` on timeout and ``WorkerDropped`` if the receiver
    is poisoned/dropped on any involved backend.
    """

    def _scan() -> Optional[Tuple[float, ChannelEnd, str]]:
        best: Optional[Tuple[float, ChannelEnd, str]] = None
        for end, peers in sources:
            if not peers:
                continue
            cand = end.earliest(peers)
            if cand is not None and (best is None or cand[0] < best[0]):
                best = (cand[0], end, cand[1])
        return best

    deadline = None if timeout is None else time.monotonic() + timeout
    while True:
        best = _scan()
        if best is not None:
            # settle: near-simultaneous wall-clock senders may not all have
            # enqueued yet — one short extra poll keeps virtual-arrival order
            # from being decided by thread scheduling (kept well under the
            # idle poll so the per-message overhead stays negligible)
            time.sleep(min(poll, 0.002))
            best = _scan() or best
            _, end, src = best
            # single-consumer mailboxes: the message seen by earliest() can
            # only be taken by us, so a short timeout is a safety net
            s, payload, arrival = end.recv_any([src], timeout=1.0)
            return end, s, payload, arrival
        for end, _ in sources:
            end.check_poison()
        if deadline is not None and time.monotonic() >= deadline:
            raise queue.Empty
        time.sleep(poll)


_BACKEND_FACTORIES: Dict[str, Callable[[], TransportBackend]] = {}


def register_backend(name: str, factory: Callable[[], TransportBackend]) -> None:
    _BACKEND_FACTORIES[name] = factory


def registered_backends() -> List[str]:
    """Names of all registered transport backends."""
    return sorted(_BACKEND_FACTORIES)


def backend_factory(name: str) -> Callable[[], TransportBackend]:
    if name not in _BACKEND_FACTORIES:
        raise KeyError(
            f"unknown backend {name!r}; registered: {sorted(_BACKEND_FACTORIES)}"
        )
    return _BACKEND_FACTORIES[name]


register_backend("inproc", lambda: InprocBackend("inproc"))
register_backend("p2p-emu", lambda: InprocBackend("p2p-emu"))
register_backend("mqtt-emu", lambda: InprocBackend("mqtt-emu", shared_broker=True))
# "collective" channels are lowered onto the mesh, not message-passed; the
# inproc instance only serves membership queries during emulation.
register_backend("collective", lambda: InprocBackend("collective"))


class ChannelManager:
    """Per-job channel fabric: instantiates one backend per channel spec and
    hands out ``ChannelEnd`` s to workers (the SDK's channel manager).

    ``backend_factory`` overrides the registry lookup with a per-spec factory
    — the hook the multiproc worker runtime uses to route *every* channel
    through its socket connection to the driver's transport hub while the
    application code keeps talking to plain ``ChannelEnd`` s.
    """

    def __init__(
        self,
        channel_specs: Sequence[ChannelSpec],
        backend_factory: Optional[Callable[[ChannelSpec], TransportBackend]] = None,
    ):
        self._specs = {c.name: c for c in channel_specs}
        self._backends: Dict[str, TransportBackend] = {}
        for c in channel_specs:
            if backend_factory is not None:
                backend = backend_factory(c)
            else:
                if c.backend not in _BACKEND_FACTORIES:
                    # the socket-backed flavors register on import of the
                    # transport package — pull it in before giving up
                    try:
                        import repro.transport  # noqa: F401
                    except ModuleNotFoundError as exc:
                        # only a genuinely absent package is survivable; a
                        # transitive import failure inside it must surface,
                        # not masquerade as "unknown backend"
                        if exc.name not in ("repro", "repro.transport"):
                            raise
                if c.backend not in _BACKEND_FACTORIES:
                    raise KeyError(
                        f"unknown backend {c.backend!r} for channel {c.name!r}; "
                        f"registered: {sorted(_BACKEND_FACTORIES)}"
                    )
                backend = _BACKEND_FACTORIES[c.backend]()
            backend.set_wire_dtype(c.name, c.wire_dtype)
            # opt-in wire codec: socket-backed transports actually run it on
            # the send path; emulation backends use it for post-codec byte
            # accounting only (their payloads never leave the process). The
            # op is deliberately outside the TransportBackend protocol.
            codec = getattr(c, "codec", "")
            set_codec = getattr(backend, "set_codec", None)
            if codec and set_codec is not None:
                set_codec(c.name, codec)
            self._backends[c.name] = backend

    def spec(self, channel: str) -> ChannelSpec:
        return self._specs[channel]

    def backend(self, channel: str) -> TransportBackend:
        return self._backends[channel]

    def end(
        self, channel: str, group: str, worker: str, join: bool = True
    ) -> ChannelEnd:
        spec = self._specs[channel]
        my_role = worker.rsplit("-", 1)[0]
        peer_role: Optional[str] = None
        a, b = spec.pair
        if a != b and my_role in (a, b):
            peer_role = b if my_role == a else a
        e = ChannelEnd(
            self._backends[channel], channel, group, worker, peer_role=peer_role
        )
        if join:
            e.join()
        return e

    def total_bytes(self, channel: str) -> float:
        return self._backends[channel].stats.get(f"bytes:{channel}", 0.0)

    def total_msgs(self, channel: str) -> int:
        """Messages moved over ``channel`` — the latency-dominated protocols
        (vertical per-batch activation exchange) are characterised by message
        count, not byte volume."""
        return int(self._backends[channel].stats.get(f"msgs:{channel}", 0))

    def channel_stats(self, channel: str) -> Dict[str, float]:
        """Per-channel wire accounting: moved bytes/messages plus — on coded
        channels — the raw (pre-codec) bytes and the achieved compression
        ratio. Emu backends report emulated post-codec bytes; the multiproc
        client reports the measured sizes of the real coded frames."""
        stats = self._backends[channel].stats
        out: Dict[str, float] = {
            "bytes": float(stats.get(f"bytes:{channel}", 0.0)),
            "msgs": float(stats.get(f"msgs:{channel}", 0.0)),
        }
        raw = stats.get(f"raw_bytes:{channel}")
        if raw:
            coded = stats.get(f"coded_bytes:{channel}", out["bytes"])
            out["raw_bytes"] = float(raw)
            out["codec_ratio"] = float(coded) / float(raw)
        # the multiproc client counts encode calls; the fan-out fast path
        # makes this O(1) per broadcast instead of O(dsts)
        encodes = stats.get(f"payload_encodes:{channel}")
        if encodes is not None:
            out["payload_encodes"] = float(encodes)
        # ...and decode calls on the receive path, so both ends of the codec
        # pipeline are observable
        decodes = stats.get(f"payload_decodes:{channel}")
        if decodes is not None:
            out["payload_decodes"] = float(decodes)
        # reduce plane: update frames folded broker-side, and the partial
        # frames that replaced them on the hub->server leg
        for key in ("hub_reduced", "hub_partials"):
            val = stats.get(f"{key}:{channel}")
            if val is not None:
                out[key] = float(val)
        # session layer: recovery counters are fabric-wide (not per-channel)
        # but surfaced here so chaos tests assert "recovery happened" off
        # the same stats dict as everything else
        for key in ("resumes:", "replays:", "dedup_hits:", "hub_restarts:"):
            val = stats.get(key)
            if val:
                out[key.rstrip(":")] = float(val)
        return out

    def codec_ratio(self, channel: str) -> Optional[float]:
        """Achieved wire-compression ratio on ``channel`` (coded / raw
        bytes), or ``None`` when no coded traffic has been observed."""
        return self.channel_stats(channel).get("codec_ratio")

    def close(self) -> None:
        """Release transports that hold OS resources (idempotent).

        Emu backends are plain objects and have no ``close``; socket-backed
        ones (the multiproc loopback owns a listening hub) must be shut down
        when the job ends or a long-lived control plane leaks fds/threads.
        """
        for backend in self._backends.values():
            close = getattr(backend, "close", None)
            if close is not None:
                try:
                    close()
                except OSError:  # pragma: no cover - teardown best-effort
                    pass
