"""Role base programs — the user programming model (§4.4, Fig. 4/5).

Base classes implement the full tasklet workflow for each standard role
(trainer, aggregator, global aggregator, …); a user subclass only fills in
``initialize / load_data / train / evaluate``. Derived topologies (CO-FL,
Hybrid) extend these with the Table 1 surgical-edit API — see
``repro.core.roles_coord`` and ``HybridTrainer`` below — without touching
this module (the paper's "no core-library changes" claim; LOC accounting for
Table 3 is done over these files in the benchmark suite).
"""
from __future__ import annotations

import abc
import queue
import time
from typing import Any, Dict, List, Optional, Tuple

from repro.core.channels import ChannelEnd, ChannelManager
from repro.core.composer import CloneComposer, Composer, Loop, Tasklet
from repro.core.expansion import WorkerConfig
# the fold's mean is part of the role API: StreamingMean and weighted_mean
# are named here too
from repro.core.fold import StreamingMean, _fold_allreduce, weighted_mean  # noqa: F401
from repro.core.tag import TAG


class RoleContext:
    """Everything a worker needs at runtime: its config, channel ends, the
    job hyperparameters and a handle on the per-channel clocks (for emulated
    compute time).

    Role bodies reach the transport exclusively through ``ChannelEnd`` — the
    context's clock helpers resolve an end first, so the same program runs
    unchanged whether the end is backed by in-process queues or by a socket
    to the multiproc transport hub.
    """

    def __init__(
        self,
        worker: WorkerConfig,
        tag: TAG,
        channels: ChannelManager,
        hyperparams: Optional[Dict[str, Any]] = None,
        static_members: Optional[Dict[str, List[str]]] = None,
    ) -> None:
        self.worker = worker
        self.tag = tag
        self.channels = channels
        self.hyperparams = dict(hyperparams or {})
        # channel -> sorted worker ids in this worker's group on that channel,
        # computed statically from the expansion (no join races).
        self.static_members = dict(static_members or {})
        self._ends: Dict[str, ChannelEnd] = {}
        self._clock_ends: Dict[str, ChannelEnd] = {}

    def end(self, channel: str) -> ChannelEnd:
        if channel not in self._ends:
            group = self.worker.group_of(channel)
            self._ends[channel] = self.channels.end(channel, group, self.worker.worker_id)
        return self._ends[channel]

    def clock_end(self, channel: str) -> ChannelEnd:
        """An end usable for clock/poison queries without joining the channel
        (a HybridTrainer non-leader models compute time on the uplink it never
        joins — joining as a side effect would corrupt the membership)."""
        if channel in self._ends:
            return self._ends[channel]
        if channel not in self._clock_ends:
            group = self.worker.group_of(channel)
            self._clock_ends[channel] = self.channels.end(
                channel, group, self.worker.worker_id, join=False
            )
        return self._clock_ends[channel]

    def advance_clock(self, channel: str, seconds: float) -> None:
        self.clock_end(channel).advance(seconds)

    def now(self, channel: str) -> float:
        return self.clock_end(channel).now()

    def set_clock(self, channel: str, at: float) -> None:
        self.clock_end(channel).set_clock(at)


def bridge_clock(ctx: "RoleContext", channel: str) -> None:
    """Carry a worker's latest virtual time onto ``channel``'s backend.

    A node on several channels (an intermediate aggregator: receiver below,
    sender above) has one clock per backend; without bridging, a send on the
    other channel would depart *before* the work that produced it finished,
    undercounting tree round times."""
    t = max(ctx.now(c) for c in ctx.worker.groups)
    ctx.set_clock(channel, t)


def await_peer(ctx: "RoleContext", end: "ChannelEnd", timeout: float = 5.0) -> str:
    """First peer on ``end``, waiting out transient empty membership.

    During a dropout/re-join window a parent briefly leaves its channels; a
    child probing ``ends()`` right then must wait for the re-join (or for its
    own orphan poison) instead of crashing on an empty peer list."""
    me = ctx.worker.worker_id
    deadline = time.monotonic() + timeout
    while True:
        peers = end.ends()
        if peers:
            return peers[0]
        end.check_poison()
        if time.monotonic() >= deadline:
            raise RuntimeError(
                f"{me}: no peer on channel {end.channel!r} after {timeout}s "
                "(did the only upstream worker drop without a re-join?)"
            )
        time.sleep(0.01)


class Role(abc.ABC):
    """Base of all role programs. ``compose()`` builds the tasklet chain,
    ``run()`` executes it."""

    def __init__(self, ctx: RoleContext) -> None:
        self.ctx = ctx
        self.config = ctx.hyperparams
        self.composer: Optional[Composer] = None
        self._work_done = False
        self.rounds = int(self.config.get("rounds", 3))
        self._round = 0
        self.metrics: List[Dict[str, float]] = []
        self._protocol: Any = None  # lazily-bound RoundProtocol

    # -------- user-implemented core functions (paper Fig. 5) ---------- #
    def initialize(self) -> None:  # pragma: no cover - overridden
        pass

    def load_data(self) -> None:  # pragma: no cover - overridden
        pass

    def train(self) -> None:  # pragma: no cover - overridden
        pass

    def evaluate(self) -> None:  # pragma: no cover - overridden
        pass

    @abc.abstractmethod
    def compose(self) -> None:
        ...

    # -------------------------- round protocol ------------------------ #
    def _protocol_channel(self) -> Optional[str]:
        """The channel whose TAG ``protocol`` attribute selects this role's
        round protocol. ``None`` (the base default) means the role has no
        protocol surface — it always resolves the ``weight-sync`` no-op."""
        return None

    def _protocol_name(self, channel: Optional[str]) -> str:
        """``round_protocol`` hyperparam > TAG channel attribute > default."""
        name = str(self.config.get("round_protocol", "") or "")
        if not name and channel is not None:
            for c in self.ctx.tag.channels_of(self.ctx.worker.role):
                if c.name == channel and getattr(c, "protocol", ""):
                    name = c.protocol
                    break
        return name or "weight-sync"

    @property
    def protocol(self) -> Any:
        """The ``RoundProtocol`` bound to this role, resolved lazily on first
        use (subclasses may rebind their protocol channel after ``__init__``,
        e.g. the auto-channel global aggregator)."""
        if self._protocol is None:
            from repro.core.protocols import make_protocol

            channel = self._protocol_channel()
            self._protocol = make_protocol(
                self._protocol_name(channel), self, channel
            )
        return self._protocol

    def pre_run(self) -> None:
        """Join this worker's channels. Runs before any chain executes (the
        runtime barriers between pre_run and run to avoid join races)."""
        for channel in self.ctx.worker.groups:
            self.ctx.end(channel)

    def run(self) -> None:
        if self.composer is None:
            self.compose()
        assert self.composer is not None
        # protocol chain surgery runs after compose() (including any subclass
        # surgery) so the protocol sees the final chain; the default
        # weight-sync protocol leaves chains untouched
        self.protocol.rewrite_chain(self.composer)
        self.composer.name_spans(self.ctx.worker.role)
        self.composer.run()

    def on_dropped(self, at: float) -> None:
        """Cancellation hook: the runtime calls this when the worker's virtual
        clock crossed its scheduled dropout time. Leaves every joined channel
        so peers' ``ends()`` stop seeing the dead worker."""
        self.metrics.append({"dropped_at": at})
        for end in list(self.ctx._ends.values()):
            end.leave()


# ====================================================================== #
# Classical / Hierarchical FL roles
# ====================================================================== #
class Trainer(Role):
    """Leaf trainer: fetch global weights, train locally, upload update.

    The *content* of fetch/upload — what crosses the wire each step — lives
    in the channel's ``RoundProtocol`` (``repro.core.protocols``); the
    default is the classic ``weight-sync`` exchange. The chain below is only
    the *shape* of a round, which is why the same Trainer class serves
    weight-sync, vertical-split and gossip topologies unchanged.
    """

    param_channel = "param-channel"

    def __init__(self, ctx: RoleContext) -> None:
        super().__init__(ctx)
        self.weights: Any = None
        self.num_samples: int = int(self.config.get("num_samples", 1))
        # staleness hook: async/deadline servers stamp their broadcasts with a
        # model version; the trainer echoes it so the server can compute the
        # update's staleness. Sync servers send no version (payloads — and so
        # the emulated wire bytes — are unchanged in sync mode).
        self._server_version: Optional[int] = None
        # a trainer on a single unconventionally-named channel (gossip ring,
        # vertical activation channel, ...) binds to it without a subclass
        chans = [c.name for c in ctx.tag.channels_of(ctx.worker.role)]
        if chans and self.param_channel not in chans and len(chans) == 1:
            self.param_channel = chans[0]

    def _protocol_channel(self) -> Optional[str]:
        return self.param_channel

    # ----------------------------- tasklets --------------------------- #
    def fetch(self) -> None:
        self.protocol.fetch()

    def upload(self) -> None:
        self.protocol.upload()

    def compose(self) -> None:
        with Composer() as composer:
            self.composer = composer
            tl_load = Tasklet("load", self.load_data)
            tl_init = Tasklet("init", self.initialize)
            tl_fetch = Tasklet("fetch", self.fetch)
            tl_train = Tasklet("train", self.train)
            tl_eval = Tasklet("evaluate", self.evaluate)
            tl_upload = Tasklet("upload", self.upload)
            loop = Loop(loop_check_fn=lambda: self._work_done)
            tl_load >> tl_init >> loop(
                tl_fetch >> tl_train >> tl_eval >> tl_upload
            )


class _AggregatorBase(Role):
    """Shared distribute/aggregate machinery for aggregator-like roles.

    Like ``Trainer``, the step *content* is the down channel's
    ``RoundProtocol`` (default ``weight-sync``: broadcast weights, fold a
    sorted-src streaming mean); this class owns only the round shape.
    """

    down_channel = "param-channel"  # towards trainers

    def __init__(self, ctx: RoleContext) -> None:
        super().__init__(ctx)
        self.weights: Any = self.config.get("init_weights")
        self.agg_weights: Any = None
        self.agg_samples: int = 0
        self._server_version: Optional[int] = None  # staleness echo (async)
        # high-water mark of client update trees held at once while folding:
        # the streaming path keeps this at 1 regardless of group size
        self.peak_buffered: int = 0

    def _protocol_channel(self) -> Optional[str]:
        return self.down_channel

    def distribute(self) -> None:
        self.protocol.distribute()

    def aggregate(self) -> None:
        self.protocol.aggregate()


class Aggregator(_AggregatorBase):
    """Intermediate aggregator of H-FL: aggregates its group, relays upward."""

    up_channel = "global-channel"

    def fetch(self) -> None:
        end = self.ctx.end(self.up_channel)
        msg = end.recv(await_peer(self.ctx, end))
        self.weights = msg["weights"]
        self._server_version = msg.get("version", self._server_version)
        self._work_done = bool(msg.get("done", False))
        bridge_clock(self.ctx, self.down_channel)

    def upload(self) -> None:
        if self._work_done:
            return
        end = self.ctx.end(self.up_channel)
        bridge_clock(self.ctx, self.up_channel)
        self.ctx.advance_clock(
            self.up_channel, float(self.config.get("compute_time", 0.0))
        )
        end.send(
            await_peer(self.ctx, end),
            self.protocol.pack_update(
                self.weights, self.agg_samples, self._server_version
            ),
        )

    def compose(self) -> None:
        with Composer() as composer:
            self.composer = composer
            tl_init = Tasklet("init", self.initialize)
            tl_fetch = Tasklet("fetch", self.fetch)
            tl_dist = Tasklet("distribute", self.distribute)
            tl_agg = Tasklet("aggregate", self.aggregate)
            tl_upload = Tasklet("upload", self.upload)
            loop = Loop(loop_check_fn=lambda: self._work_done)
            tl_init >> loop(tl_fetch >> tl_dist >> tl_agg >> tl_upload)


class GlobalAggregator(_AggregatorBase):
    """Root aggregator: drives the rounds and owns the global model."""

    def __init__(self, ctx: RoleContext) -> None:
        super().__init__(ctx)
        if self.weights is None:
            self.weights = self.config.get("init_weights")

    down_channel = "param-channel"

    def check_rounds(self) -> None:
        self._round += 1
        self.metrics.append({"round": self._round})
        if self._round >= self.rounds:
            self._work_done = True

    def end_of_train(self) -> None:
        if self._work_done:
            # final broadcast tells everyone to exit their loops
            self.distribute()

    def compose(self) -> None:
        with Composer() as composer:
            self.composer = composer
            tl_init = Tasklet("init", self.initialize)
            tl_dist = Tasklet("distribute", self.distribute)
            tl_agg = Tasklet("aggregate", self.aggregate)
            tl_eval = Tasklet("evaluate", self.evaluate)
            tl_round = Tasklet("check_rounds", self.check_rounds)
            tl_end = Tasklet("end_of_train", self.end_of_train)
            loop = Loop(loop_check_fn=lambda: self._work_done)
            tl_init >> loop(
                tl_dist >> tl_agg >> tl_eval >> tl_round
            ) >> tl_end


class HFLGlobalAggregator(GlobalAggregator):
    """Global aggregator of H-FL: same workflow, down channel is the
    aggregator-facing channel."""

    down_channel = "global-channel"


# Alias used by hierarchical template (global sits on "global-channel")
class _AutoChannelGlobalAggregator(GlobalAggregator):
    def __init__(self, ctx: RoleContext) -> None:
        super().__init__(ctx)
        chans = [c.name for c in ctx.tag.channels_of(ctx.worker.role)]
        # prefer the conventional names, else the only channel present
        for preferred in ("global-channel", "param-channel"):
            if preferred in chans:
                self.down_channel = preferred
                break
        else:
            self.down_channel = chans[0]


# The original (pre-alias) root-aggregator class: the runtime uses this to
# recognize "root of the aggregation tree" programs when lowering a TAG to a
# deadline/async execution policy (see repro.core.roles_async).
GlobalAggregatorBase = GlobalAggregator

# Make GlobalAggregator channel-aware by default.
GlobalAggregator = _AutoChannelGlobalAggregator  # type: ignore[misc]


# ====================================================================== #
# Distributed / Hybrid roles
# ====================================================================== #
class DistributedTrainer(Trainer):
    """Distributed learning (Fig 2b): ring all-reduce among trainers,
    no aggregator. Reuses the Trainer chain; fetch/upload are replaced by an
    allreduce tasklet via the Table 1 API — the "Δ inheritance" of Table 4."""

    ring_channel = "ring-channel"

    def __init__(self, ctx: RoleContext) -> None:
        super().__init__(ctx)
        # no aggregator to fetch initial weights from: start from the job's
        # init_weights (every trainer starts identically)
        if self.weights is None:
            self.weights = self.config.get("init_weights")

    def allreduce(self) -> None:
        end = self.ctx.end(self.ring_channel)
        # deterministic exchange: send in sorted-peer order and drain one
        # mailbox per peer in the same order (recv_fifo's arrival-order drain
        # broke virtual-time ties by wall-clock thread timing), then fold in
        # sorted worker-id order — ring results are run-to-run reproducible
        # on every backend by construction, not by downstream sorting alone
        peers = sorted(end.ends())
        update = {"weights": self.weights, "num_samples": self.num_samples}
        for peer in peers:
            end.send(peer, update)
        received = [(src, end.recv(src)) for src in peers]
        self.weights, _ = _fold_allreduce(
            end.me, self.weights, float(self.num_samples), received
        )
        self._round += 1
        if self._round >= self.rounds:
            self._work_done = True

    def compose(self) -> None:
        super().compose()
        assert self.composer is not None
        with CloneComposer(self.composer) as composer:
            self.composer = composer
            tl_ar = Tasklet("allreduce", self.allreduce)
            composer.get_tasklet("fetch").remove()
            composer.get_tasklet("upload").replace_with(tl_ar)


class HybridTrainer(Trainer):
    """Hybrid FL (Fig 2e): intra-cluster all-reduce on the fast P2P channel;
    only the cluster leader uploads to / fetches from the global aggregator.

    Leadership is *elected*, not static: the leader is the lowest-ranked
    **live** member of the cluster (static expansion order filtered by ring
    membership), so a cluster survives its leader dropping mid-round — the
    next member takes over the uplink on the following step. Each round the
    leader's in-cluster re-broadcast pins the round *cohort* (the members
    participating in this round's all-reduce) and a monotonically increasing
    ``cluster_round`` stamp; the all-reduce exchanges only within the pinned
    cohort and discards stale stamps, so a worker re-joining mid-round syncs
    up at the next round broadcast instead of corrupting the current fold.

    Known limitation: a leader that drops *after* the aggregator sent it the
    round weights but *before* its in-cluster re-broadcast loses that
    broadcast; under a sync (barriered) aggregator the cluster then only
    recovers at the next round's distribute. Deadline/async uplink policies
    tolerate the skipped round by design.
    """

    ring_channel = "ring-channel"

    def __init__(self, ctx: RoleContext) -> None:
        super().__init__(ctx)
        self._cluster_round = 0
        self._cohort: List[str] = []
        self._said_hello = False

    def _live_members(self) -> List[str]:
        """Static cluster members filtered to the ones currently on the ring
        (in static order — rank survives dropouts and re-joins)."""
        me = self.ctx.worker.worker_id
        end = self.ctx.end(self.ring_channel)
        live = set(end.ends()) | {me}
        static = self.ctx.static_members.get(self.ring_channel)
        if static:
            return [m for m in static if m in live]
        return sorted(live)

    def _cluster_rank(self) -> Tuple[int, List[str]]:
        members = self._live_members()
        return members.index(self.ctx.worker.worker_id), members

    def pre_run(self) -> None:
        """Non-leaders never join the uplink channel, so the aggregator's
        ``ends()`` sees exactly one leader per cluster."""
        self.ctx.end(self.ring_channel)
        rank, _ = self._cluster_rank()
        if rank == 0:
            self.ctx.end(self.param_channel)

    def cluster_allreduce(self) -> None:
        if self._work_done:
            return
        end = self.ctx.end(self.ring_channel)
        me = end.me
        cohort = [m for m in (self._cohort or self._live_members()) if m != me]
        if not cohort:
            self._cluster_samples = self.num_samples
            self._cluster_round += 1
            return
        update = {
            "weights": self.weights,
            "num_samples": self.num_samples,
            "cluster_round": self._cluster_round,
        }
        live = set(end.ends())
        for peer in sorted(cohort):
            if peer in live:  # skip cohort members that already dropped
                end.send(peer, update)
        received = []
        for src in sorted(cohort):  # sorted per-src drain: deterministic
            msg = self._recv_cluster(end, src)
            if msg is not None:
                received.append((src, msg))
        self.weights, self._cluster_samples = _fold_allreduce(
            me, self.weights, float(self.num_samples), received
        )
        self._cluster_round += 1

    def _recv_cluster(self, end: ChannelEnd, src: str) -> Optional[Dict[str, Any]]:
        """One cohort member's round-stamped all-reduce contribution.

        Tolerates mid-round dropout (``None``: fold without the dead member)
        and skips stale messages — leftover round broadcasts share the
        leader's mailbox, and a re-joined worker's mailbox can hold
        contributions from rounds it missed."""
        deadline = time.monotonic() + float(self.config.get("grace", 30.0))
        while True:
            try:
                msg = end.recv(src, timeout=0.25)
            except queue.Empty:
                end.check_poison()
                if src not in end.ends():
                    return None  # dropped mid-round
                if time.monotonic() >= deadline:
                    raise RuntimeError(
                        f"{end.me}: cluster member {src!r} sent no round-"
                        f"{self._cluster_round} all-reduce contribution"
                    )
                continue
            if "members" in msg:
                continue  # a round broadcast this worker already moved past
            if "hello" in msg:
                if int(msg["hello"]) < self._cluster_round:
                    # a fresh incarnation of ``src`` (re-joined mid-job): it
                    # never saw this round's broadcast, so no contribution is
                    # coming — fold without it; it syncs at the next round
                    return None
                continue  # cold-start hello; src will still contribute
            if int(msg.get("cluster_round", self._cluster_round)) != self._cluster_round:
                continue  # stale contribution from a missed round
            return msg

    def fetch(self) -> None:
        """The elected leader fetches from the aggregator and re-broadcasts
        in-cluster with the round cohort pinned; everyone else waits for the
        broadcast, re-electing whenever the current leader drops."""
        ring = self.ctx.end(self.ring_channel)
        if not self._said_hello:
            # first fetch of this incarnation (cold start OR a fresh program
            # after a re-join): announce it, so a peer mid-all-reduce stops
            # waiting for a contribution this incarnation never saw the round
            # broadcast for (FIFO order guarantees the hello is drained
            # before anything this incarnation sends later)
            hello = {"hello": self._cluster_round}
            for m in self._live_members():
                if m != ring.me:
                    ring.send(m, hello)
            self._said_hello = True
        deadline = time.monotonic() + float(self.config.get("grace", 30.0))
        while True:
            rank, members = self._cluster_rank()
            if rank == 0:
                super().fetch()  # joins the uplink on first election
                self._cohort = members
                bcast = {
                    "weights": self.weights,
                    "done": self._work_done,
                    "cluster_round": self._cluster_round,
                    "members": members,
                }
                # relay the server version so a member promoted to leader
                # mid-job echoes it on its first upload (deadline/async
                # uplink policies discard unstamped updates)
                if self._server_version is not None:
                    bcast["version"] = self._server_version
                ring.broadcast(bcast)
                return
            try:
                msg = ring.recv(members[0], timeout=0.25)
            except queue.Empty:
                ring.check_poison()
                if time.monotonic() >= deadline:
                    raise RuntimeError(
                        f"{ring.me}: no round broadcast from cluster leader "
                        f"{members[0]!r}"
                    )
                continue  # leader may have dropped: re-elect and retry
            if "members" not in msg:
                continue  # an all-reduce leftover from a round this worker missed
            if int(msg.get("cluster_round", 0)) < self._cluster_round:
                continue  # stale round broadcast
            self.weights = msg["weights"]
            self._work_done = bool(msg.get("done", False))
            self._server_version = msg.get("version", self._server_version)
            self._cluster_round = int(msg.get("cluster_round", self._cluster_round))
            self._cohort = list(msg.get("members", members))
            return

    def upload(self) -> None:
        """Only the cluster leader uploads one cluster-level model. The
        leader is re-resolved against the round cohort's *live* members, so
        a mid-round leader dropout promotes the next cohort member."""
        if self._work_done:
            return
        me = self.ctx.worker.worker_id
        ring = self.ctx.end(self.ring_channel)
        live = set(ring.ends()) | {me}
        leaders = [m for m in (self._cohort or [me]) if m in live]
        if not leaders or leaders[0] != me:
            return
        end = self.ctx.end(self.param_channel)  # a promoted leader joins here
        end.send(
            await_peer(self.ctx, end),
            self.protocol.pack_update(
                self.weights,
                getattr(self, "_cluster_samples", self.num_samples),
                self._server_version,
            ),
        )

    def compose(self) -> None:
        super().compose()
        assert self.composer is not None
        with CloneComposer(self.composer) as composer:
            self.composer = composer
            tl_ar = Tasklet("cluster_allreduce", self.cluster_allreduce)
            composer.get_tasklet("upload").insert_before(tl_ar)
