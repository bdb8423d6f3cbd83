"""Asynchronous / semi-synchronous aggregator programs (root + intermediate).

These are the lowering targets of ``repro.core.runtime.RuntimePolicy``: the
same TAG whose aggregation tree is built from ``GlobalAggregator`` /
``Aggregator`` subclasses executes

* ``mode="sync"``     — the classic barriered rounds (unchanged base class);
* ``mode="deadline"`` — semi-sync partial participation: each round closes at
  a straggler deadline on the virtual clock; late updates are excluded (and
  discarded by model-version check when they eventually arrive);
* ``mode="async"``    — FedBuff-style buffered async aggregation (Nguyen et
  al. 2022): the server reacts to whichever trainer finishes first, weights
  each update by its staleness, and applies the buffer every K updates.

Policy lowering is *hierarchy-wide*: ``RuntimePolicy.tiers`` assigns a mode
per role, so an intermediate H-FL aggregator can collect from its group under
its own deadline (``DeadlineAggregatorMixin``) or FedBuff buffer
(``AsyncAggregatorMixin``) and relay staleness-annotated partial aggregates
upward, independent of the root's mode. Version vectors propagate down with
broadcasts (root version echoed upward, local sub-version echoed by trainers)
so every tier staleness-weights correctly.

``make_policy_program(base_cls, mode)`` grafts the matching mixin family onto
the user's aggregator class — root mixins for ``GlobalAggregator`` subclasses,
intermediate mixins for ``Aggregator`` subclasses — so user-defined
``initialize``/``evaluate`` hooks survive the policy lowering: the paper's
"deployment detail, not application logic" claim extended to execution
semantics over the whole aggregation tree.
"""
from __future__ import annotations

import json
import os
import queue
import time
from typing import Any, Dict, List, Optional, Tuple, Type

import numpy as np

from repro import checkpoint
from repro.core.channels import WorkerDropped, recv_any_multi
from repro.core.composer import Composer, Loop, Tasklet
from repro.core.protocols import pack_broadcast, pack_update
from repro.core.fold import StreamingMean
from repro.core.roles import Role, await_peer, bridge_clock


def _tree_sub(a: Any, b: Any) -> Any:
    import jax

    return jax.tree_util.tree_map(lambda x, y: np.asarray(x) - np.asarray(y), a, b)


def _tree_copy(t: Any) -> Any:
    import jax

    return jax.tree_util.tree_map(np.asarray, t)


def _json_py(o: Any) -> Any:
    """JSON fallback keeping checkpointed logs equal (under ``==``) to the
    live ones: numpy scalars to their python counterparts."""
    if isinstance(o, np.integer):
        return int(o)
    if isinstance(o, np.floating):
        return float(o)
    if isinstance(o, np.ndarray):
        return o.tolist()
    raise TypeError(f"not JSON-serializable: {type(o).__name__}")


class _SnapshotStore:
    """Bounded per-version weight snapshots for staleness-based deltas.

    A policy server needs the snapshot a trainer *trained from* to compute
    the update's delta. Keeping every version leaks memory over a long async
    run, so the store keeps only versions within the maximum staleness
    observed so far (plus a one-version safety margin) and clamps requests
    for evicted versions to the oldest retained snapshot, reporting the
    clamp so the caller can log the *effective* staleness it weighted with.
    """

    def __init__(self) -> None:
        self._snaps: Dict[int, Any] = {}
        self._window = 1  # max staleness observed so far

    def __len__(self) -> int:
        return len(self._snaps)

    def versions(self) -> List[int]:
        return sorted(self._snaps)

    @property
    def window(self) -> int:
        return self._window

    def put(self, version: int, weights: Any, keep_from: Optional[int] = None) -> None:
        """Store ``version`` and evict what no live client can still need.

        ``keep_from`` is the version-vector floor: the oldest version any
        currently-tracked client was last handed (minus an in-flight margin).
        Without it, eviction falls back to the observed-staleness window
        alone, and a straggler past the window gets a clamped base."""
        self._snaps[version] = weights
        floor = version - self._window - 1
        if keep_from is not None:
            floor = min(floor, keep_from)
        for v in [v for v in self._snaps if v < floor]:
            del self._snaps[v]

    def base_for(self, trained_from: int, current: int) -> Tuple[Any, int, bool]:
        """``(base_weights, effective_staleness, clamped)`` for an update that
        trained from ``trained_from`` while the server is at ``current``."""
        if trained_from in self._snaps:
            staleness = max(0, current - trained_from)
            clamped = False
        else:
            trained_from = min(self._snaps)
            staleness = max(0, current - trained_from)
            clamped = True
        self._window = max(self._window, staleness)
        return self._snaps[trained_from], staleness, clamped


class _PolicyBase:
    """Shared policy plumbing for the deadline/async mixins (any tier)."""

    def __init__(self, ctx) -> None:
        super().__init__(ctx)
        # Policy mixins lower *how* weight-sync rounds run; they do not (yet)
        # lower other round protocols. Fail fast at program-build time rather
        # than deadlock mid-round on a protocol whose message flow the mixin
        # does not speak.
        declared = str(self.config.get("round_protocol", "") or "")
        if not declared:
            for c in ctx.tag.channels_of(ctx.worker.role):
                if getattr(c, "protocol", "") and c.protocol != "weight-sync":
                    declared = c.protocol
                    break
        if declared and declared != "weight-sync":
            raise RuntimeError(
                f"runtime policies (deadline/async) only lower the "
                f"'weight-sync' round protocol, but role "
                f"{ctx.worker.role!r} declares {declared!r}; run this "
                "topology under the sync policy"
            )

    def _policy(self) -> Any:
        pol = self.config.get("runtime_policy")
        if pol is None:
            raise RuntimeError("policy-lowered aggregator needs 'runtime_policy'")
        # per-tier parameter overrides: a tiers entry may be a dict like
        # {"mode": "deadline", "deadline": 1.5} — resolve this role's view so
        # an edge tier can run a tighter deadline than the core
        return pol.for_role(self.ctx.worker.role)

    def _down(self):
        return self.ctx.end(self.down_channel)

    def _trainers(self) -> List[str]:
        return sorted(self._down().ends())

    # ------------------------- checkpoint-restart ---------------------- #
    # Periodic crash checkpoints via ``repro.checkpoint``, keyed by the
    # ``checkpoint_every`` / ``checkpoint_dir`` hyperparams (both required
    # to enable). Each policy server persists under its own subdirectory so
    # every tier of a lowered hierarchy checkpoints independently.
    def _ckpt_every(self) -> int:
        return int(self.config.get("checkpoint_every", 0) or 0)

    def _ckpt_dir(self) -> Optional[str]:
        base = str(self.config.get("checkpoint_dir", "") or "")
        if self._ckpt_every() <= 0 or not base:
            return None
        return os.path.join(base, self.ctx.worker.worker_id)

    def _ckpt_state(self) -> Dict[str, Any]:  # pragma: no cover - overridden
        raise NotImplementedError

    def _maybe_checkpoint(self) -> None:
        """Persist the server state tree every ``checkpoint_every`` versions
        (atomic, step-indexed — see ``repro.checkpoint``)."""
        directory = self._ckpt_dir()
        if directory is None or self._version % self._ckpt_every() != 0:
            return
        checkpoint.save(directory, self._version, self._ckpt_state())

    def _collect_deadline(
        self, expected: List[str], version: int, round_start: float
    ) -> Tuple[List[Tuple[str, Any, float]], List[Tuple[str, Any, float]], set, float]:
        """Drain ``version``-stamped updates from ``expected`` until the
        straggler deadline (virtual clock) or the wall-clock grace expires.

        Returns ``(on_time, late, remaining, round_end)`` — each update as
        ``(src, msg, arrival)`` — after advancing this worker's down-channel
        clock to the round end (and honoring its own dropout schedule)."""
        pol = self._policy()
        deadline = round_start + float(pol.deadline)
        end = self._down()
        remaining = set(expected)
        arrived: List[Tuple[str, Any, float]] = []
        grace_end = time.monotonic() + float(pol.grace)
        while remaining:
            timeout = grace_end - time.monotonic()
            if timeout <= 0:
                break
            # peers already scheduled to drop before this round's deadline
            # can still have delivered (or be mid-delivery of) an on-time
            # update — keep draining, but only wait briefly for them.
            # Read drop_time once per peer: a concurrent re-join clears the
            # schedule between two reads (TOCTOU -> None > float TypeError)
            live = []
            for t in remaining:
                drop_at = end.drop_time(t)
                if drop_at is None or drop_at > deadline:
                    live.append(t)
            if not live:
                timeout = min(timeout, 0.25)
            try:
                src, msg, arrival = end.recv_any(
                    sorted(remaining), timeout=timeout, advance=False
                )
            except queue.Empty:
                if not live:
                    break
                continue
            if msg.get("version") != version:
                continue  # stale leftover from a missed deadline: discard
            arrived.append((src, msg, arrival))
            remaining.discard(src)

        on_time = [a for a in arrived if a[2] <= deadline]
        late = [a for a in arrived if a[2] > deadline]
        # partial-participation floor: admit the earliest stragglers if the
        # deadline left too few updates (extends the round past the deadline)
        need = max(0, int(pol.min_participants) - len(on_time))
        if need:
            late.sort(key=lambda a: a[2])
            on_time.extend(late[:need])
            late = late[need:]

        # the round closes at the deadline when anyone was cut or missing,
        # else at the last on-time arrival
        cut = bool(late) or bool(remaining)
        last_arrival = max((a[2] for a in on_time), default=round_start)
        round_end = max(deadline if cut else last_arrival, last_arrival)
        if not np.isfinite(round_end):
            round_end = last_arrival
        me = self.ctx.worker.worker_id
        end.set_clock(round_end)
        drop_at = end.drop_time()
        if drop_at is not None and round_end > drop_at:
            raise WorkerDropped(me, drop_at)
        return on_time, late, remaining, round_end


class _DeadlineBase(_PolicyBase):
    """Shared round plumbing of the deadline root and intermediate mixins:
    version-stamped round opening, deadline-bounded collection with
    participation logging, and the sub-round version counter."""

    def __init__(self, ctx) -> None:
        super().__init__(ctx)
        self._version = 0
        self._round_start = 0.0
        self._expected: List[str] = []
        self.participation_log: List[Dict[str, Any]] = []

    def _open_round(self, done: bool = False) -> None:
        """Stamp the current weights with the sub-round version (echoed by
        the group, used to discard leftovers from missed deadlines) and
        start the round clock."""
        end = self._down()
        self._expected = self._trainers()
        self._round_start = self.ctx.now(self.down_channel)
        end.send_many(
            self._expected, pack_broadcast(self.weights, done, self._version)
        )

    def _close_round(self) -> None:
        """Collect under the deadline, fold the on-time updates into the
        model, log participation and bump the sub-round version."""
        on_time, late, remaining, round_end = self._collect_deadline(
            self._expected, self._version, self._round_start
        )
        # fold in sorted-src order, not arrival order: virtual-arrival ties
        # are broken by wall-clock thread timing, so an arrival-order fold
        # would make seeded deadline rounds drift by an ulp run-to-run.
        # The fold itself streams — one scaled tree at a time into the O(1)
        # accumulator (the deadline window necessarily retains this round's
        # arrivals for on-time/late classification; the fold adds only one
        # more tree on top, not another O(C))
        acc = StreamingMean()
        for _, m, _ in sorted(on_time, key=lambda a: a[0]):
            acc.fold(m["weights"], float(m.get("num_samples", 1)))
        agg, total = acc.finalize()
        if agg is not None:
            self.agg_weights = agg
            self.agg_samples = int(total)
            self.weights = agg
        else:
            # nothing arrived on time: keep the current model and carry zero
            # sample weight so an upstream tier ignores the relay
            self.agg_samples = 0
        self.participation_log.append(
            {
                "round": self._version,
                "included": sorted(s for s, _, _ in on_time),
                "excluded": sorted(s for s, _, _ in late),
                "missing": sorted(remaining),
                "round_time": round_end - self._round_start,
            }
        )
        self.peak_buffered = max(self.peak_buffered, acc.peak_buffered)
        # same observability surface as the sync aggregate step: fold counts
        # and peak buffering land in job-result metrics. Policy collection
        # classifies each update individually (on-time vs late, per-update
        # versions), so the hub-reduce plane never applies here — the
        # ``reduce_plan`` hyperparam falls back to per-frame transparently
        # and ``agg_frames`` always equals ``agg_folds``.
        self.metrics.append({
            "agg_folds": acc.count,
            "agg_frames": acc.count,
            "peak_buffered": self.peak_buffered,
        })
        self._version += 1
        self._maybe_checkpoint()

    def _ckpt_state(self) -> Dict[str, Any]:
        meta = {
            "participation_log": self.participation_log,
            "metrics": self.metrics,
        }
        return {
            "weights": self.weights,
            "version": np.int64(self._version),
            "meta": np.array(json.dumps(meta, default=_json_py)),
        }


class DeadlineRootMixin(_DeadlineBase):
    """Per-round straggler deadline on the virtual clock (semi-sync root)."""

    # --------------------------- tasklets ----------------------------- #
    def begin_round(self) -> None:
        self._open_round()

    def collect(self) -> None:
        self._close_round()

    def check_rounds(self) -> None:
        if not self.participation_log:
            raise RuntimeError(
                "DeadlineRootMixin.check_rounds ran with an empty "
                "participation_log: the deadline workflow requires "
                "begin_round >> collect before check_rounds — did a subclass "
                "reorder the tasklet chain?"
            )
        self._round += 1
        self.metrics.append(
            {
                "round": self._round,
                "round_time": self.participation_log[-1]["round_time"],
            }
        )
        if self._round >= self.rounds:
            self._work_done = True

    def end_of_train(self) -> None:
        end = self._down()
        end.send_many(self._trainers(), pack_broadcast(self.weights, True))

    def compose(self) -> None:
        with Composer() as composer:
            self.composer = composer
            tl_init = Tasklet("init", self.initialize)
            tl_begin = Tasklet("begin_round", self.begin_round)
            tl_collect = Tasklet("collect", self.collect)
            tl_eval = Tasklet("evaluate", self.evaluate)
            tl_round = Tasklet("check_rounds", self.check_rounds)
            tl_end = Tasklet("end_of_train", self.end_of_train)
            loop = Loop(loop_check_fn=lambda: self._work_done)
            tl_init >> loop(
                tl_begin >> tl_collect >> tl_eval >> tl_round
            ) >> tl_end


class _BufferedAsyncBase(_PolicyBase):
    """Shared FedBuff machinery of the async root and async intermediate."""

    def __init__(self, ctx) -> None:
        super().__init__(ctx)
        self._version = 0
        self._snapshots = _SnapshotStore()
        self._strategy = None
        self._strategy_state = None
        self._greeted: set = set()
        # client -> last version handed to it (the downward version vector);
        # bounds snapshot eviction so a slow client's base stays available
        self._version_vector: Dict[str, int] = {}
        self.staleness_log: List[Dict[str, Any]] = []
        # high-water mark of unabsorbed delta trees held at once: the
        # streaming absorb folds each delta into strategy state at arrival,
        # so this stays 1 regardless of client count or buffer size
        self.peak_buffered = 0

    def _init_strategy(self) -> None:
        from repro.fl.strategies import get_strategy

        pol = self._policy()
        name = str(self.config.get("async_strategy", "fedbuff"))
        if name == "fedbuff":
            self._strategy = get_strategy(
                "fedbuff",
                buffer_size=int(pol.buffer_size),
                server_lr=float(self.config.get("server_lr", 1.0)),
                staleness_exp=float(pol.staleness_exp),
            )
        elif name == "fedasync":
            self._strategy = get_strategy(
                "fedasync",
                alpha=float(self.config.get("async_alpha", 0.6)),
                staleness_exp=float(pol.staleness_exp),
            )
        else:
            raise ValueError(
                f"async mode needs a buffered strategy, got {name!r} "
                "(one of: fedbuff, fedasync)"
            )
        self._strategy_state = self._strategy.init(self.weights)

    def _send_weights(self, end, client: str, version: int, done: bool = False) -> None:
        """Send the current weights to ``client`` and record the handed-out
        version in the version vector (drives snapshot retention)."""
        self._version_vector[client] = version
        end.send(client, pack_broadcast(self.weights, done, version))

    def _snapshot_floor(self) -> int:
        """Oldest version a tracked client may still be training from: its
        last handed version minus one (an upload based on the *previous*
        hand-out can still be in flight when a new one is sent)."""
        if not self._version_vector:
            return self._version
        return min(self._version_vector.values()) - 1

    def _prune_version_vector(self, members: set) -> None:
        """Forget clients that left the channel so a dead straggler cannot
        pin old snapshots in memory forever."""
        for t in [t for t in self._version_vector if t not in members]:
            del self._version_vector[t]

    def _flush_threshold(self) -> int:
        """Updates per buffer application (FedBuff's buffer size; 1 for
        FedAsync). The streaming absorb no longer defers to a flush — the
        strategy's ``ready`` fires at this same count — but the threshold
        remains the observable "updates per version" knob."""
        return max(1, int(getattr(self._strategy, "buffer_size", 1)))

    def _absorb(self, src: str, msg: Any, arrival: float) -> bool:
        """Fold one update straight into strategy state via
        ``accumulate_stream`` (the streaming O(1) absorption path); when the
        strategy reports a full buffer, apply it, bump the local version and
        snapshot. Returns True when a new version was produced.

        The delta and its staleness are resolved at *arrival* against the
        snapshot the sender trained from, and the weighted accumulation
        happens at arrival too — no delta tree is ever retained, so server
        memory is O(1) in client count and buffer size. The strategy's
        ``ready`` check fires when ``count`` reaches the buffer size, and
        the streaming fold runs the ops of the incremental ``accumulate``
        chain, so absorbed versions and weights are those of that chain."""
        # an unstamped update (sync-tier sender) counts as fresh, not maximal
        trained_from = int(msg.get("version", self._version))
        base, staleness, clamped = self._snapshots.base_for(
            trained_from, self._version
        )
        delta = _tree_sub(msg["weights"], base)
        entry = {
            "src": src, "staleness": staleness, "version": self._version,
            "arrival": arrival,
        }
        if clamped:
            entry["clamped"] = True
        self.staleness_log.append(entry)
        self.peak_buffered = max(self.peak_buffered, 1)
        self._strategy_state = self._strategy.accumulate_stream(
            self._strategy_state, delta, int(staleness)
        )
        if not bool(self._strategy.ready(self._strategy_state)):
            return False
        new_w, self._strategy_state = self._strategy.apply(
            self.weights, None, self._strategy_state
        )
        self.weights = _tree_copy(new_w)
        self._version += 1
        self._snapshots.put(
            self._version, self.weights, keep_from=self._snapshot_floor()
        )
        self._maybe_checkpoint()
        return True

    def _ckpt_state(self) -> Dict[str, Any]:
        """The full FedBuff server state as one checkpointable tree: model
        weights, version + version vector, the snapshot store, streaming
        strategy state ({"acc": tree, "count": int32} — arrays throughout),
        and the JSON-able observables (logs/metrics) as a 0-d string leaf,
        so a restore reproduces the server's *observable* history too."""
        meta = {
            "staleness_log": self.staleness_log,
            "metrics": self.metrics,
            "round": int(getattr(self, "_round", 0)),
            "peak_buffered": int(self.peak_buffered),
            "snapshot_window": int(self._snapshots.window),
        }
        return {
            "weights": self.weights,
            "version": np.int64(self._version),
            "version_vector": {
                c: np.int64(v) for c, v in self._version_vector.items()
            },
            "snapshots": {
                str(v): w for v, w in self._snapshots._snaps.items()
            },
            "strategy": self._strategy_state,
            "meta": np.array(json.dumps(meta, default=_json_py)),
        }

    def _restore_latest(self) -> bool:
        """Crash recovery: rebuild the server from its newest checkpoint.

        Returns False (cold start) when checkpointing is off or no step has
        been written yet. On restore the whole state tree — weights,
        version/version vector, snapshot store, strategy accumulator, logs
        — comes back from disk, a ``restored_step`` metric marks the
        resume, and the greeting set is reset so the caller re-admits every
        live client with the restored weights (a duplicate broadcast is
        harmless: trainers just train from it again)."""
        directory = self._ckpt_dir()
        if directory is None:
            return False
        step = checkpoint.latest_step(directory)
        if step is None:
            return False
        tree = checkpoint.load_tree(directory, step)
        meta = json.loads(str(np.asarray(tree["meta"])))
        self.weights = tree["weights"]
        self._version = int(np.asarray(tree["version"]))
        self._version_vector = {
            c: int(np.asarray(v))
            for c, v in tree.get("version_vector", {}).items()
        }
        self._snapshots._snaps = {
            int(v): w for v, w in tree["snapshots"].items()
        }
        self._snapshots._window = int(meta["snapshot_window"])
        self._strategy_state = tree["strategy"]
        self.staleness_log = list(meta["staleness_log"])
        self.metrics = list(meta["metrics"])
        self._round = int(meta["round"])
        self.peak_buffered = int(meta["peak_buffered"])
        self._greeted = set()
        self.metrics.append({"restored_step": int(step)})
        return True


class AsyncRootMixin(_BufferedAsyncBase):
    """FedBuff-style buffered asynchronous aggregation at the root.

    The server is purely reactive: it processes updates in virtual-arrival
    order (``recv_any``), weights each by staleness (server version now minus
    version the client trained from), and applies the buffered average every
    ``buffer_size`` updates. Trainers never barrier — each gets fresh weights
    back immediately after its upload is absorbed.
    """

    def bootstrap(self) -> None:
        self._init_strategy()
        if self._restore_latest():
            # restarted server: re-admit the live cohort through the session
            # layer — every current trainer gets the restored weights (and
            # version), so an upload lost to the crash is simply re-trained
            end = self._down()
            self._greeted = set(self._trainers())
            for t in sorted(self._greeted):
                self._send_weights(end, t, self._version)
            return
        self._snapshots.put(0, _tree_copy(self.weights))
        end = self._down()
        self._greeted = set(self._trainers())
        for t in sorted(self._greeted):
            self._send_weights(end, t, 0)
        # step-0 checkpoint: a crash before the first version restores here
        self._maybe_checkpoint()

    def _target_versions(self) -> int:
        pol = self._policy()
        if pol.max_updates is not None:
            return int(pol.max_updates)
        return self.rounds

    def serve(self) -> None:
        pol = self._policy()
        end = self._down()
        trainers = self._trainers()
        if not trainers:
            self._work_done = True  # everyone dropped: nothing left to serve
            return
        # greet members that joined (or re-joined) since the last look at the
        # channel: dynamic membership — they start from the current weights
        current = set(trainers)
        for t in sorted(current - self._greeted):
            self._send_weights(end, t, self._version)
        self._greeted = current  # forget leavers so a re-join is greeted again
        self._prune_version_vector(current)
        try:
            src, msg, arrival = end.recv_any(trainers, timeout=float(pol.grace))
        except queue.Empty:
            if set(self._trainers()) != current:
                return  # membership changed while waiting: re-greet first
            # No update within the wall-clock grace window. This can mean
            # "everyone is gone" OR "real training is slower than grace" —
            # record the early stop so an under-trained result is
            # distinguishable from a completed run.
            self.metrics.append(
                {
                    "early_stop": True,
                    "version": self._version,
                    "target_versions": self._target_versions(),
                }
            )
            self._work_done = True
            return
        # a zero-sample relay (an intermediate whose whole group missed its
        # deadline) carries no training content: absorbing it would fill a
        # buffer slot, dilute the flushed aggregate and advance the version
        # on nothing — skip it, but still hand fresh weights back
        if float(msg.get("num_samples", 1)) > 0 and self._absorb(src, msg, arrival):
            self._round = self._version
            self.evaluate()
            self.metrics.append({"round": self._version, "virtual_time": arrival})
            if self._version >= self._target_versions():
                self._work_done = True
                return
        # hand the uploader fresh weights so it keeps training (no barrier)
        self._send_weights(end, src, self._version)

    def finish(self) -> None:
        end = self._down()
        end.send_many(self._trainers(), pack_broadcast(self.weights, True))

    def compose(self) -> None:
        with Composer() as composer:
            self.composer = composer
            tl_init = Tasklet("init", self.initialize)
            tl_boot = Tasklet("bootstrap", self.bootstrap)
            tl_serve = Tasklet("serve", self.serve)
            tl_finish = Tasklet("finish", self.finish)
            loop = Loop(loop_check_fn=lambda: self._work_done)
            tl_init >> tl_boot >> loop(tl_serve) >> tl_finish


# ====================================================================== #
# Intermediate-aggregator mixins (hierarchy-wide lowering)
# ====================================================================== #
class DeadlineAggregatorMixin(_DeadlineBase):
    """Per-sub-round straggler deadline for an intermediate aggregator.

    Keeps the base ``Aggregator`` chain shape (fetch >> distribute >>
    aggregate >> upload), so it interoperates with *any* root policy: only
    the group collection is deadline-bounded. Broadcasts stamp a local
    sub-round version (echoed by the trainers, used to discard leftovers
    from missed deadlines) while uploads echo the root's version — set by
    the base ``Aggregator.fetch`` — so the root staleness-weights the
    relayed aggregate correctly.
    """

    def distribute(self) -> None:
        self._open_round(done=self._work_done)

    def aggregate(self) -> None:
        if self._work_done:
            return  # peers were just told to exit; nothing will arrive
        self._close_round()

class AsyncAggregatorMixin(_BufferedAsyncBase):
    """FedBuff-style buffered aggregation at an intermediate tier.

    The node is simultaneously a receiver (trainer updates on the down
    channel) and a sender (partial aggregates on the up channel):
    ``serve()`` multiplexes both directions in virtual-arrival order via
    ``recv_any_multi``. Trainer staleness is measured against the node's
    *local* sub-version; every buffer flush relays the partial aggregate
    upward annotated with the flushed updates' staleness
    (``tier_staleness``) and the last root version seen (``version``), so
    the root's own staleness weighting stays correct. A root broadcast
    rebases the node: the new global weights become the next local version.
    """

    def __init__(self, ctx) -> None:
        super().__init__(ctx)
        self._root_version: Optional[int] = None
        self._buffer_samples = 0.0
        self._buffer_staleness: List[int] = []
        self.relay_log: List[Dict[str, Any]] = []

    def _up(self):
        return self.ctx.end(self.up_channel)

    def bootstrap(self) -> None:
        up = self._up()
        msg = up.recv(await_peer(self.ctx, up))
        self.weights = msg["weights"]
        self._root_version = msg.get("version")
        self._work_done = bool(msg.get("done", False))
        self._init_strategy()
        bridge_clock(self.ctx, self.down_channel)
        self._snapshots.put(0, _tree_copy(self.weights))
        if self._work_done:
            return
        end = self._down()
        self._greeted = set(self._trainers())
        for t in sorted(self._greeted):
            self._send_weights(end, t, 0)

    def serve(self) -> None:
        pol = self._policy()
        down = self._down()
        up = self._up()
        trainers = self._trainers()
        current = set(trainers)
        for t in sorted(current - self._greeted):
            self._send_weights(down, t, self._version)
        self._greeted = current
        self._prune_version_vector(current)
        roots = up.ends()
        sources = [(down, sorted(current)), (up, sorted(roots))]
        try:
            end, src, msg, arrival = recv_any_multi(
                sources, timeout=float(pol.grace)
            )
        except queue.Empty:
            if set(self._trainers()) != current:
                return  # membership changed while waiting: re-greet first
            if current or roots:
                self.metrics.append({"early_stop": True, "version": self._version})
                # a barriered root above would block forever on this silent
                # exit: relay once to unblock its current round, then leave
                # so later rounds skip us. Partially-accumulated updates
                # (strategy count below the buffer size) were streamed into
                # strategy state but never applied to self.weights, so the
                # relay must carry
                # zero sample weight or the root would overweight a stale
                # model by the unapplied updates' sample counts
                self._buffer_samples = 0.0
                self._buffer_staleness = []
                self._relay_up()
            self._work_done = True
            up.leave()
            return
        if end is up:
            # root direction: rebase on the new global model
            self.weights = msg["weights"]
            self._root_version = msg.get("version", self._root_version)
            self._work_done = bool(msg.get("done", False))
            if self._work_done:
                return
            self._version += 1
            self._snapshots.put(
                self._version, _tree_copy(self.weights),
                keep_from=self._snapshot_floor(),
            )
            bridge_clock(self.ctx, self.down_channel)
            return
        # trainer direction: buffer the update; on flush, relay upward
        # (zero-sample updates carry no content — skip, as the root does)
        if float(msg.get("num_samples", 1)) > 0:
            self._buffer_samples += float(msg.get("num_samples", 1))
            flushed = self._absorb(src, msg, arrival)
            self._buffer_staleness.append(int(self.staleness_log[-1]["staleness"]))
            if flushed:
                self._relay_up()
        self._send_weights(down, src, self._version)

    def _relay_up(self) -> None:
        up = self._up()
        roots = up.ends()
        if not roots:
            return
        bridge_clock(self.ctx, self.up_channel)
        self.ctx.advance_clock(
            self.up_channel, float(self.config.get("compute_time", 0.0))
        )
        update: Dict[str, Any] = pack_update(
            self.weights, int(self._buffer_samples), self._root_version
        )
        update["tier_staleness"] = list(self._buffer_staleness)
        up.send(roots[0], update)
        self.relay_log.append(
            {
                "version": self._version,
                "num_samples": int(self._buffer_samples),
                "tier_staleness": list(self._buffer_staleness),
                "root_version": self._root_version,
            }
        )
        self._buffer_samples = 0.0
        self._buffer_staleness = []

    def finish(self) -> None:
        end = self._down()
        end.send_many(self._trainers(), pack_broadcast(self.weights, True))

    def compose(self) -> None:
        with Composer() as composer:
            self.composer = composer
            tl_init = Tasklet("init", self.initialize)
            tl_boot = Tasklet("bootstrap", self.bootstrap)
            tl_serve = Tasklet("serve", self.serve)
            tl_finish = Tasklet("finish", self.finish)
            loop = Loop(loop_check_fn=lambda: self._work_done)
            tl_init >> tl_boot >> loop(tl_serve) >> tl_finish


_PROGRAM_CACHE: Dict[Tuple[type, str], type] = {}

_ROOT_MIXINS: Dict[str, type] = {
    "deadline": DeadlineRootMixin,
    "async": AsyncRootMixin,
}

_AGG_MIXINS: Dict[str, type] = {
    "deadline": DeadlineAggregatorMixin,
    "async": AsyncAggregatorMixin,
}


def make_policy_program(base_cls: Type[Role], mode: str) -> Type[Role]:
    """Graft the policy mixin for ``mode`` onto an aggregator class.

    Root aggregators (``GlobalAggregator`` subclasses) get the root mixin
    family; intermediate H-FL aggregators (``Aggregator`` subclasses) get the
    intermediate family, so the whole aggregation tree lowers tier by tier.
    """
    from repro.core.roles import Aggregator, GlobalAggregatorBase

    if issubclass(base_cls, GlobalAggregatorBase):
        family = _ROOT_MIXINS
    elif issubclass(base_cls, Aggregator):
        family = _AGG_MIXINS
    else:
        raise TypeError(
            f"cannot policy-lower {base_cls.__name__}: not a GlobalAggregator "
            "or Aggregator subclass"
        )
    if mode not in family:
        raise ValueError(f"unknown policy mode {mode!r}; known: {sorted(family)}")
    key = (base_cls, mode)
    if key not in _PROGRAM_CACHE:
        mixin = family[mode]
        _PROGRAM_CACHE[key] = type(
            f"{mode.capitalize()}{base_cls.__name__}", (mixin, base_cls), {}
        )
    return _PROGRAM_CACHE[key]
