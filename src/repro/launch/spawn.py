"""Multi-process job launcher: an expanded TAG as a real process tree (§5.3).

This is the driver/worker split of the multiproc transport:

* the **driver** (this process) expands the JobSpec, starts a
  ``TransportHub`` owning all channel state, spawns one OS process per
  worker, and collects a ``JobResult``;
* each **worker process** rebuilds its ``RoleContext`` against a
  ``ChannelManager`` whose every channel routes through a socket to the hub
  (``MultiprocBackend``) and runs its role program unchanged — the same
  classes that run threaded against ``InprocBackend``.

Two deployment knobs scale this past one-process-per-worker (the paper's
10k-trainer trees cannot pay a process and a broker round-trip per worker):

* ``pool_size=N`` runs every logical worker on one of N recycled **pool
  hosts** (``_HostPool``): a host pays interpreter/import cost once and runs
  each assigned worker as a thread, so job start-up cost is O(pool) instead
  of O(workers). Event-driven jobs keep their lazy start — a worker's task
  is queued to a host only when the ``EventEngine`` fires its arrival event.
* ``sharded=True`` partitions the hub by the TAG's groupBy labels
  (``ShardedTransportHub``): one broker per group plus a root for
  cross-shard channels, the paper's per-group MQTT broker model (§6.2).

A seeded sync job therefore produces byte-identical global weights on both
deployments (the transport-layer acceptance criterion); what changes is the
deployment, never the application logic.

Event-driven jobs — deadline/async ``RuntimePolicy`` modes, dropout and
re-join schedules — run here too: the driver binds the deployment-agnostic
``EventEngine`` (``repro.core.events``) to a hub-side **process supervisor**.
Dropout is enforced hub-side (``set_drop`` on the shared backend) so a
worker's ``WorkerDropped`` surfaces inside its own process exactly like the
threaded runtime; the supervisor maps the engine's directives onto the
process tree — orphan cascade via hub-side ``poison``, re-join via a task
assignment to a pre-warmed standby host, so respawn latency is not bounded
by interpreter start-up. The standby pool is shared and sized by the
concurrent-dropout high-water mark (``_rejoin_high_water``), not one parked
process per scheduled re-join. Policy servers (deadline/FedBuff) run
unchanged because role bodies reach the transport only through
``ChannelEnd``.
"""
from __future__ import annotations

import dataclasses
import multiprocessing
import queue as queue_mod
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

from repro.core.channels import ChannelManager, LinkModel, WorkerDropped
from repro.core.events import EventEngine
from repro.core.expansion import JobSpec, WorkerConfig, expand
from repro.core.registry import ResourceRegistry
from repro.core.roles import GlobalAggregatorBase, RoleContext
from repro.core.runtime import (
    JobResult,
    RuntimePolicy,
    resolve_policy_class,
    resolve_program,
    static_membership,
    validate_policy_tiers,
)
from repro.transport.multiproc import (
    ShardedTransportHub,
    TransportHub,
    make_backend_factory,
)

__all__ = ["MultiprocLauncher", "RemoteProgram", "run_job_multiproc"]


@dataclasses.dataclass
class RemoteProgram:
    """Driver-side stub for a program that ran in a worker process.

    Carries the result surface back across the process boundary: ``weights``
    and ``metrics`` always; the policy-server observables (participation /
    staleness / relay logs, server version, version vector) when the worker
    ran a policy-lowered aggregator — the same attributes the in-process
    runtime exposes, so cross-deployment equivalence tests read one surface.
    ``is_root`` records the worker-side ``isinstance(prog,
    GlobalAggregatorBase)`` verdict so ``JobResult.global_weights`` resolves
    the root without the class."""

    worker_id: str
    role: str
    weights: Any = None
    metrics: List[Dict[str, Any]] = dataclasses.field(default_factory=list)
    is_root: bool = False
    participation_log: List[Dict[str, Any]] = dataclasses.field(default_factory=list)
    staleness_log: List[Dict[str, Any]] = dataclasses.field(default_factory=list)
    relay_log: List[Dict[str, Any]] = dataclasses.field(default_factory=list)
    version: Optional[int] = None
    version_vector: Dict[str, int] = dataclasses.field(default_factory=dict)


def _program_summary(prog: Any) -> Dict[str, Any]:
    """The result surface marshalled from a worker process to the driver."""
    summary: Dict[str, Any] = {
        "weights": getattr(prog, "weights", None),
        "metrics": list(getattr(prog, "metrics", [])),
        "is_root": isinstance(prog, GlobalAggregatorBase),
    }
    for log in ("participation_log", "staleness_log", "relay_log"):
        if hasattr(prog, log):
            summary[log] = list(getattr(prog, log))
    if hasattr(prog, "_version"):
        summary["version"] = int(prog._version)
    if hasattr(prog, "_version_vector"):
        summary["version_vector"] = dict(prog._version_vector)
    return summary


def _remote_program(wid: str, role: str, summary: Dict[str, Any]) -> RemoteProgram:
    return RemoteProgram(
        worker_id=wid,
        role=role,
        weights=summary.get("weights"),
        metrics=list(summary.get("metrics", [])),
        is_root=bool(summary.get("is_root", False)),
        participation_log=list(summary.get("participation_log", [])),
        staleness_log=list(summary.get("staleness_log", [])),
        relay_log=list(summary.get("relay_log", [])),
        version=summary.get("version"),
        version_vector=dict(summary.get("version_vector", {})),
    )


def _worker_body(
    address: Any,
    job: JobSpec,
    worker: WorkerConfig,
    hyperparams: Dict[str, Any],
    static_members: Dict[str, List[str]],
    program_cls: Optional[type],
    barrier: Any,
    result_q: Any,
    barrier_timeout: float,
    policy: Optional[RuntimePolicy] = None,
    drop_ack: Any = None,
) -> None:
    """One logical worker's run, deployment-agnostic on the worker side.

    Called either as the whole body of a dedicated spawned process
    (``_worker_entry``) or on a thread of a recycled pool host
    (``_pool_host_entry``) — the transport keeps both flavors equivalent
    because every channel op is an RPC keyed by ``worker_id``, never by
    process identity. ``address`` is a single hub address or a shard
    address map (``make_backend_factory`` dispatches); ``barrier`` is None
    for dynamically-joining workers (late arrivals and re-join respawns of
    an event-driven job); ``drop_ack`` is anything with ``.wait(timeout)``
    (an ``mp.Event`` for a dedicated process, a ``threading.Event`` routed
    by the pool host's ack dispatcher).

    Dropout is a two-phase report: a ``dropping`` notice goes up *before*
    ``on_dropped`` leaves the channels, and the worker waits on ``drop_ack``
    until the driver has recorded the drop and poisoned any orphans — so a
    child probing its peers sees either its parent or the poison, never a
    limbo state (the same ordering the threaded runtime enforces)."""
    worker_id = worker.worker_id
    pol = policy or RuntimePolicy()
    passed_barrier = False
    channels: Optional[ChannelManager] = None
    try:
        channels = ChannelManager(
            job.tag.channels,
            backend_factory=make_backend_factory(address, client_key=worker_id),
        )
        if pol.is_lowering:
            overrides = {worker.role: program_cls} if program_cls is not None else {}
            cls = resolve_policy_class(worker, pol, overrides)
            hyperparams = dict(hyperparams)
            hyperparams.setdefault("runtime_policy", pol)
        else:
            cls = program_cls if program_cls is not None else resolve_program(worker.program)
        ctx = RoleContext(
            worker, job.tag, channels,
            hyperparams=hyperparams, static_members=static_members,
        )
        prog = cls(ctx)
        prog.pre_run()
        # same barrier the threaded runtime enforces between pre_run and run:
        # no worker may see a half-joined group
        if barrier is not None:
            barrier.wait(timeout=barrier_timeout)
        passed_barrier = True
        try:
            prog.run()
        except WorkerDropped as e:
            # mid-round dropout, enforced hub-side on the virtual clock.
            # Phase 1: announce the drop and wait for the driver to record
            # it and cascade orphans (poison) BEFORE this worker leaves its
            # channels; the ack wait is bounded so a dead driver cannot
            # wedge the worker.
            result_q.put((worker_id, "dropping", float(e.at)))
            if drop_ack is not None:
                drop_ack.wait(timeout=5.0)
            try:
                prog.on_dropped(e.at)
            except BaseException as hook_err:  # noqa: BLE001
                result_q.put((
                    worker_id, "err",
                    (type(hook_err).__name__, f"on_dropped hook failed: {hook_err}"),
                ))
                return
            # phase 2: final state; the supervisor now finishes the worker
            # or signals the re-join standby
            result_q.put((worker_id, "dropped", (float(e.at), _program_summary(prog))))
            return
        result_q.put((worker_id, "ok", _program_summary(prog)))
    except BaseException as exc:  # noqa: BLE001 - marshalled to the driver
        # Pre-barrier failure: break the start barrier so healthy peers fail
        # fast (as BrokenBarrierError) instead of waiting out the whole job
        # timeout for a party that will never arrive. Post-barrier failures
        # must NOT abort: every party has already arrived, and an abort can
        # race peers still *draining* the released barrier — they would wake
        # to a broken barrier and report BrokenBarrierError in place of
        # their real error.
        if barrier is not None and not passed_barrier:
            try:
                barrier.abort()
            except Exception:
                pass
        try:
            result_q.put((worker_id, "err", (type(exc).__name__, str(exc))))
        except Exception:
            pass
    finally:
        # pool hosts outlive many logical workers: release this worker's hub
        # sockets here instead of leaning on process exit
        if channels is not None:
            try:
                channels.close()
            except Exception:
                pass


def _pin_to_cpu() -> None:
    """First statement of every spawned worker process: the driver process
    owns the accelerator, and a chip belongs to one process at a time, so
    workers run JAX on the CPU. Applied before the worker touches any JAX
    backend; ``jax_platforms`` is read when the first backend starts."""
    import jax

    jax.config.update("jax_platforms", "cpu")


def _worker_entry(
    address: Any,
    job: JobSpec,
    worker: WorkerConfig,
    hyperparams: Dict[str, Any],
    static_members: Dict[str, List[str]],
    program_cls: Optional[type],
    barrier: Any,
    result_q: Any,
    barrier_timeout: float,
    policy: Optional[RuntimePolicy] = None,
    drop_ack: Any = None,
) -> None:
    """Entry point of a dedicated (one-worker) spawned process."""
    _pin_to_cpu()
    _worker_body(
        address, job, worker, hyperparams, static_members, program_cls,
        barrier, result_q, barrier_timeout, policy, drop_ack,
    )


def _pool_host_entry(
    address: Any,
    job: JobSpec,
    membership: Dict[Tuple[str, str], List[str]],
    task_q: Any,
    ack_q: Any,
    result_q: Any,
    barrier: Any,
    barrier_timeout: float,
    policy: Optional[RuntimePolicy],
) -> None:
    """Entry point of a recycled pool-host process.

    The host pays its interpreter/import/jax start-up cost exactly once,
    then serves logical-worker assignments from ``task_q`` until the driver
    sends the ``None`` sentinel: each task ``(worker, hp_overrides,
    program_cls, use_barrier)`` starts a ``_worker_body`` thread. Results
    flow up the shared ``result_q`` exactly as from dedicated processes.

    ``ack_q`` carries the driver's drop acknowledgements; a dispatcher
    thread routes each acked worker id to that worker's local event (several
    hosted workers can be mid-dropout at once, so a single shared event
    would misdeliver). Hyperparameters arrive as per-worker *overrides* and
    are merged over ``job.hyperparams`` here — the big shared entries (e.g.
    ``init_weights``) cross the process boundary once per host, not once
    per worker."""
    _pin_to_cpu()
    acks: Dict[str, threading.Event] = {}
    acks_lock = threading.Lock()

    def _ack_loop() -> None:
        while True:
            try:
                wid = ack_q.get()
            except (EOFError, OSError):
                return
            if wid is None:
                return
            with acks_lock:
                ev = acks.get(str(wid))
            if ev is not None:
                ev.set()

    threading.Thread(target=_ack_loop, name="pool-host-ack", daemon=True).start()
    while True:
        try:
            task = task_q.get()
        except (EOFError, OSError):
            return
        if task is None:
            return  # driver teardown sentinel
        worker, overrides, program_cls, use_barrier = task
        hp = dict(job.hyperparams)
        hp.update(overrides or {})
        static = {
            ch: membership[(ch, group)] for ch, group in worker.groups.items()
        }
        ack = threading.Event()
        with acks_lock:
            acks[worker.worker_id] = ack
        threading.Thread(
            target=_worker_body,
            args=(
                address, job, worker, hp, static, program_cls,
                barrier if use_barrier else None, result_q, barrier_timeout,
                policy, ack,
            ),
            name=f"flame-{worker.worker_id}",
            daemon=True,
        ).start()


def _rejoin_high_water(policy: RuntimePolicy) -> int:
    """Standby-pool size: the high-water mark of concurrently-pending
    re-joins. Each scheduled re-join contributes a ``[drop_at, rejoin_at)``
    window during which a warm host must be on hand; a sweep over the window
    edges gives the maximum overlap. Hosts run workers as threads, so this
    is a warmth knob (how many re-joins can land without paying interpreter
    start-up), never a correctness bound — disjoint windows share one host
    where the old scheme parked one process per scheduled re-join."""
    marks: List[Tuple[float, int]] = []
    for wid, rejoin_at in policy.rejoins.items():
        drop_at = float(policy.dropouts.get(wid, 0.0))
        lo, hi = drop_at, max(float(rejoin_at), drop_at)
        marks.append((lo, 1))
        marks.append((hi, -1))
    # at equal times the freed slot serves the newly-opened window
    marks.sort(key=lambda m: (m[0], m[1]))
    cur = peak = 0
    for _, delta in marks:
        cur += delta
        peak = max(peak, cur)
    return max(peak, 1 if policy.rejoins else 0)


class _HostPool:
    """Driver-side pool of recycled worker-host processes.

    Each host (``_pool_host_entry``) is one OS process that pays its
    start-up cost once, then runs any number of logical workers as threads
    assigned over its private task queue. The launcher uses the pool two
    ways:

    * **whole-deployment pooling** (``pool_size=N``): every logical worker
      of the job runs on one of N recycled hosts, so process start-up cost
      is O(pool) instead of O(workers) — the knob that makes 1k-worker jobs
      land with near-flat wall-clock (see ``benchmarks/bench_spawn.py``);
    * **shared re-join standby pool** of the classic one-process-per-worker
      deployment, sized by ``_rejoin_high_water`` instead of one pre-warmed
      standby per scheduled re-join; a re-join becomes a task assignment to
      a warm host (same latency class as the old parked-standby signal).

    Assignment picks the least-loaded live host. Hosts are multi-threaded,
    so pool size is a warmth/parallelism knob, never a correctness bound.
    """

    def __init__(
        self,
        launcher: "MultiprocLauncher",
        address: Any,
        result_q: Any,
        barrier: Any,
        barrier_timeout: float,
        size: int,
    ) -> None:
        self._lock = threading.Lock()
        self._hosts: List[Dict[str, Any]] = []
        self._owner: Dict[str, Dict[str, Any]] = {}
        for i in range(max(1, int(size))):
            task_q = launcher._ctx.Queue()
            ack_q = launcher._ctx.Queue()
            proc = launcher._ctx.Process(
                target=_pool_host_entry,
                args=(
                    address, launcher.job, launcher._membership, task_q,
                    ack_q, result_q, barrier, barrier_timeout, launcher.policy,
                ),
                name=f"flame-pool-host-{i}",
                daemon=True,
            )
            proc.start()
            self._hosts.append(
                {"proc": proc, "task_q": task_q, "ack_q": ack_q, "load": 0}
            )

    # ------------------------------------------------------------------ #
    def assign(
        self,
        worker: WorkerConfig,
        hp_overrides: Optional[Dict[str, Any]],
        program_cls: Optional[type],
        use_barrier: bool,
    ) -> Any:
        """Queue one logical worker onto the least-loaded live host; returns
        the host process (the liveness handle crash detection watches)."""
        with self._lock:
            live = [h for h in self._hosts if h["proc"].is_alive()]
            host = min(live or self._hosts, key=lambda h: h["load"])
            host["load"] += 1
            self._owner[worker.worker_id] = host
        host["task_q"].put(
            (worker, dict(hp_overrides or {}), program_cls, bool(use_barrier))
        )
        return host["proc"]

    def owns(self, wid: str) -> bool:
        return wid in self._owner

    def procs(self) -> List[Any]:
        with self._lock:
            return [h["proc"] for h in self._hosts]

    def ack(self, wid: str) -> None:
        """Route a drop acknowledgement to the host running ``wid``."""
        with self._lock:
            host = self._owner.get(wid)
        if host is not None:
            host["ack_q"].put(wid)

    def release(self, wid: str) -> None:
        """A hosted worker reached a terminal state: free its load slot so
        later assignments (re-joins) balance onto the emptiest host."""
        with self._lock:
            host = self._owner.pop(wid, None)
            if host is not None:
                host["load"] = max(0, host["load"] - 1)

    def close(self) -> None:
        with self._lock:
            hosts, self._hosts = self._hosts, []
            self._owner.clear()
        for h in hosts:
            # sentinel first: an idle host exits on its own; _reap then
            # terminates anything still busy (or already-dead queues)
            for q in (h["task_q"], h["ack_q"]):
                try:
                    q.put_nowait(None)
                except Exception:
                    pass
        MultiprocLauncher._reap([h["proc"] for h in hosts])
        for h in hosts:
            for q in (h["task_q"], h["ack_q"]):
                try:
                    q.close()
                except Exception:
                    pass


class MultiprocLauncher:
    """Expand + deploy + run a JobSpec as one OS process per worker.

    Any ``RuntimePolicy`` runs here — the classic barriered sync execution
    and the event-driven modes (deadline / async-FedBuff, dropout and
    re-join schedules). The policy is a deployment-independent input: the
    same job produces matching participation sets and lifecycle events on
    the threaded in-process runtime and on this process tree.

    ``wall_clock`` controls the hub's clock mapping. Default: wall-clock
    time is folded into the virtual clocks for plain sync jobs (real elapsed
    time stays observable), while event-driven jobs run pure virtual clocks
    — the same clock semantics as the in-process event runtime, which is
    what makes dropout/deadline schedules mean the same thing on both
    deployments.

    Scale knobs (both pure deployment choices — seeded observables are
    byte-identical with them on or off):

    * ``pool_size``: run logical workers on this many recycled pool hosts
      (``_HostPool``) instead of one OS process each.
    * ``sharded``: partition the hub by the TAG's groupBy labels
      (``ShardedTransportHub``); a TAG with no labels degrades to the
      single hub.
    """

    def __init__(
        self,
        job: JobSpec,
        registry: Optional[ResourceRegistry] = None,
        link_models: Optional[Dict[Tuple[str, str], LinkModel]] = None,
        per_worker_hyperparams: Optional[Dict[str, Dict[str, Any]]] = None,
        program_overrides: Optional[Dict[str, type]] = None,
        policy: Optional[RuntimePolicy] = None,
        start_method: str = "spawn",
        wall_clock: Optional[bool] = None,
        pool_size: Optional[int] = None,
        sharded: bool = False,
    ) -> None:
        self.job = job
        self.workers = expand(job, registry)
        self.link_models = dict(link_models or {})
        self.per_worker_hyperparams = dict(per_worker_hyperparams or {})
        self.program_overrides = dict(program_overrides or {})
        self.policy = policy or RuntimePolicy()
        validate_policy_tiers(self.policy, job.tag)
        self.wall_clock = (
            wall_clock if wall_clock is not None else not self.policy.is_event_driven
        )
        self.pool_size = None if pool_size is None else max(1, int(pool_size))
        self.sharded = bool(sharded)
        self._shard_keys = (
            sorted({g for c in job.tag.channels for g in c.group_by})
            if self.sharded
            else []
        )
        # "spawn" keeps children clear of the driver's jax/thread state; the
        # override exists for hosts where spawn is unavailable
        self._ctx = multiprocessing.get_context(start_method)
        self._membership = static_membership(self.workers, job.tag)

    # ------------------------------------------------------------------ #
    def _make_hub(self) -> Any:
        """The job's broker fabric: one ``TransportHub``, or — when sharding
        is requested and the TAG declares groupBy labels — a
        ``ShardedTransportHub`` with one hub per label plus a root for
        cross-shard channels. Both expose the same driver surface
        (``worker_address``/``engine_transport``/``stats``/config)."""
        if self._shard_keys:
            hub: Any = ShardedTransportHub(
                self._shard_keys, wall_clock=self.wall_clock
            )
        else:
            hub = TransportHub(wall_clock=self.wall_clock)
        for c in self.job.tag.channels:
            hub.set_wire_dtype(c.name, c.wire_dtype)
        for (channel, worker), model in self.link_models.items():
            hub.set_link(channel, worker, model)
        faults = getattr(self.policy, "faults", None)
        if faults is not None:
            hub.arm_faults(faults)
        return hub

    def _worker_args(
        self, w: WorkerConfig, address: Any, barrier: Any,
        result_q: Any, barrier_timeout: float, drop_ack: Any = None,
    ) -> Tuple[Any, ...]:
        hp = dict(self.job.hyperparams)
        hp.update(self.per_worker_hyperparams.get(w.worker_id, {}))
        static = {
            ch: self._membership[(ch, group)] for ch, group in w.groups.items()
        }
        return (
            address, self.job, w, hp, static,
            self.program_overrides.get(w.role), barrier, result_q, barrier_timeout,
            self.policy, drop_ack,
        )

    def _spawn(
        self, w: WorkerConfig, address: Any, barrier: Any,
        result_q: Any, barrier_timeout: float, drop_ack: Any = None,
    ) -> Any:
        p = self._ctx.Process(
            target=_worker_entry,
            args=self._worker_args(
                w, address, barrier, result_q, barrier_timeout, drop_ack,
            ),
            name=f"flame-{w.worker_id}",
            daemon=True,
        )
        p.start()
        return p

    @staticmethod
    def _reap(procs: List[Any]) -> None:
        """Hard stop: a hung child must never wedge the driver (or CI)."""
        for p in procs:
            p.join(timeout=5.0)
            if p.is_alive():
                p.terminate()
                p.join(timeout=5.0)
            if p.is_alive():  # pragma: no cover - last resort
                p.kill()
                p.join(timeout=5.0)

    # ------------------------------------------------------------------ #
    def run(self, timeout: float = 120.0) -> JobResult:
        if self.policy.is_event_driven:
            return self._run_events(timeout)
        return self._run_sync(timeout)

    # ------------------------------------------------------------------ #
    # classic barriered sync deployment
    # ------------------------------------------------------------------ #
    def _run_sync(self, timeout: float) -> JobResult:
        hub = self._make_hub()
        result_q = self._ctx.Queue()
        barrier = self._ctx.Barrier(len(self.workers))
        procs: Dict[str, Any] = {}
        pool: Optional[_HostPool] = None
        programs: Dict[str, Any] = {}
        errors: Dict[str, BaseException] = {}
        deadline = time.monotonic() + timeout
        try:
            if self.pool_size is not None:
                pool = _HostPool(
                    self, hub.worker_address, result_q, barrier, timeout,
                    min(self.pool_size, len(self.workers)),
                )
                for w in self.workers:
                    procs[w.worker_id] = pool.assign(
                        w,
                        self.per_worker_hyperparams.get(w.worker_id, {}),
                        self.program_overrides.get(w.role),
                        use_barrier=True,
                    )
            else:
                for w in self.workers:
                    procs[w.worker_id] = self._spawn(
                        w, hub.worker_address, barrier, result_q, timeout
                    )

            # drain results before joining: a child blocks on its queue
            # feeder thread until the driver consumes its (possibly large)
            # weights payload
            pending = {w.worker_id for w in self.workers}
            by_id = {w.worker_id: w for w in self.workers}

            def _absorb(wid: str, status: str, payload: Any) -> None:
                pending.discard(wid)
                if status == "ok":
                    programs[wid] = _remote_program(wid, by_id[wid].role, payload)
                else:
                    etype, emsg = payload
                    errors[wid] = RuntimeError(f"[{etype}] {emsg}")

            while pending:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    break
                try:
                    item = result_q.get(timeout=min(remaining, 0.5))
                except queue_mod.Empty:
                    if all(procs[wid].is_alive() for wid in pending):
                        continue
                    # a pending worker died: give its (possibly still
                    # buffered) result one more poll, then fast-fail the
                    # whole tree instead of waiting out the job timeout
                    try:
                        item = result_q.get(timeout=0.5)
                    except queue_mod.Empty:
                        break
                _absorb(*item)

            # final sweep: a worker may have exited between the Empty poll
            # and the liveness check with its result still buffered in the
            # queue's pipe — don't misreport it as result-less
            while pending:
                try:
                    item = result_q.get(timeout=0.5)
                except queue_mod.Empty:
                    break
                _absorb(*item)

            if pending:
                crashed = sorted(
                    wid for wid in pending if not procs[wid].is_alive()
                )
                alive = sorted(wid for wid in pending if procs[wid].is_alive())
                if alive and not crashed:
                    errors["__timeout__"] = TimeoutError(
                        f"{len(alive)} worker processes still running after "
                        f"{timeout}s: {alive}"
                    )
                for wid in crashed:
                    errors.setdefault(wid, RuntimeError(
                        f"worker process {wid!r} exited without a result "
                        f"(exitcode={procs[wid].exitcode})"
                    ))
                for wid in alive:
                    if crashed:
                        # fast-fail: a peer crashed without reporting, so the
                        # survivors can never complete — tear the tree down
                        errors.setdefault(wid, RuntimeError(
                            f"worker process {wid!r} torn down after peer "
                            f"crash: {crashed}"
                        ))
                    else:
                        errors.setdefault(wid, TimeoutError(
                            f"worker process {wid!r} hung past the {timeout}s "
                            "deadline (killed by the driver)"
                        ))
        finally:
            if pool is not None:
                pool.close()
            else:
                self._reap(list(procs.values()))
            result_q.close()
            hub.close()

        return self._finalize(hub, programs, errors)

    # ------------------------------------------------------------------ #
    # event-driven deployment: hub-side process supervisor
    # ------------------------------------------------------------------ #
    def _run_events(self, timeout: float) -> JobResult:
        hub = self._make_hub()
        engine = EventEngine(
            self.policy, self.workers,
            spec_of=self.job.tag.channel, transport=hub.engine_transport,
        )
        supervisor = _ProcessSupervisor(self, hub, engine, timeout)
        try:
            engine.arm_dropouts()
            supervisor.prespawn_standbys()
            handles = {
                w.worker_id: _ProcessWorkerHandle(supervisor, w)
                for w in self.workers
            }
            engine.bind(handles)
            supervisor.start_pump()
            alive = engine.run(timeout=timeout)
            supervisor.stop_pump()
            errors = supervisor.errors
            if alive:
                # pending (not programs) is the terminal-state ledger: a
                # re-joined worker's pre-dropout summary already sits in
                # programs, and a hung respawn must still surface as a
                # timeout, not as silent stale state
                still = sorted(
                    wid for wid in alive
                    if wid in supervisor.pending and wid not in errors
                )
                if still:
                    errors["__timeout__"] = TimeoutError(
                        f"{len(still)} worker processes still running after "
                        f"{timeout}s: {still}"
                    )
                    for wid in still:
                        errors[wid] = TimeoutError(
                            f"worker process {wid!r} hung past the {timeout}s "
                            "deadline (killed by the driver)"
                        )
        finally:
            supervisor.close()
            hub.close()

        return self._finalize(
            hub, supervisor.programs, supervisor.errors,
            dropped=engine.dropped, events=engine.events,
        )

    # ------------------------------------------------------------------ #
    def _finalize(
        self,
        hub: Any,
        programs: Dict[str, Any],
        errors: Dict[str, BaseException],
        dropped: Optional[Dict[str, float]] = None,
        events: Optional[List[Tuple[float, str, str]]] = None,
    ) -> JobResult:
        # hub.stats merges across shards on a sharded fabric: each channel
        # topic lives on exactly one hub, so the sums equal single-hub totals
        stats = hub.stats
        channel_bytes = {
            c.name: stats.get(f"bytes:{c.name}", 0.0)
            for c in self.job.tag.channels
        }
        for w in self.workers:  # stubs for workers that returned nothing
            programs.setdefault(
                w.worker_id, RemoteProgram(worker_id=w.worker_id, role=w.role)
            )
        # surface the fabric's recovery counters on the root program (the
        # way agg_folds rides program metrics), so tests assert that
        # recovery actually happened instead of attribute-poking the hub
        recovery = {
            key.rstrip(":"): float(stats[key])
            for key in ("resumes:", "replays:", "dedup_hits:", "hub_restarts:")
            if stats.get(key)
        }
        if recovery:
            for prog in programs.values():
                if getattr(prog, "is_root", False):
                    prog.metrics.append({"transport_recovery": recovery})
                    break
        return JobResult(
            workers=self.workers,
            programs=programs,
            channel_bytes=channel_bytes,
            errors=errors,
            dropped=dict(dropped or {}),
            events=list(events or []),
        )


class _ProcessSupervisor:
    """Driver-side supervision state for an event-driven process tree.

    Owns the result-queue pump (a daemon thread feeding worker outcomes to
    the ``EventEngine``), the per-worker process table, the re-join standby
    pool (or, with ``pool_size`` set, the whole host pool every worker runs
    on), and the fast-fail teardown for workers that die without
    reporting."""

    def __init__(
        self,
        launcher: MultiprocLauncher,
        hub: Any,
        engine: EventEngine,
        timeout: float,
    ) -> None:
        self.launcher = launcher
        self.hub = hub
        self.engine = engine
        self.timeout = timeout
        self.deadline = time.monotonic() + timeout
        self.result_q = launcher._ctx.Queue()
        self.by_id = {w.worker_id: w for w in launcher.workers}
        initial = {w.worker_id for w in engine.initial_cohort()}
        self.initial = initial
        self.barrier = launcher._ctx.Barrier(len(initial)) if initial else None
        self.procs: Dict[str, Any] = {}        # wid -> live/most-recent process
        # whole-deployment host pool (pool_size) — every worker runs here
        self.pool: Optional[_HostPool] = None
        if launcher.pool_size is not None:
            self.pool = _HostPool(
                launcher, hub.worker_address, self.result_q, self.barrier,
                timeout, min(launcher.pool_size, max(1, len(launcher.workers))),
            )
        # classic deployment's shared re-join standby pool (see
        # prespawn_standbys); None when pooled — the pool hosts are the
        # warm standbys already
        self.standby_pool: Optional[_HostPool] = None
        self.drop_acks: Dict[str, Any] = {}    # wid -> dedicated process's ack
        # wid -> engine re-join directive recorded at the "dropping" phase
        self._rejoin_at: Dict[str, Optional[float]] = {}
        self.programs: Dict[str, Any] = {}
        self.errors: Dict[str, BaseException] = {}
        self.pending: set = set(self.by_id)
        self.done: Dict[str, threading.Event] = {
            wid: threading.Event() for wid in self.by_id
        }
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._pump_thread: Optional[threading.Thread] = None

    # ------------------------------ spawn ------------------------------ #
    def prespawn_standbys(self) -> None:
        """Pre-warm the shared re-join standby pool: ``_rejoin_high_water``
        hosts pay their interpreter/import cost now (concurrently with the
        job), so a re-join lands milliseconds after the engine's directive
        instead of a full process start-up later. With whole-deployment
        pooling there is nothing to do — every host is already warm."""
        if self.pool is not None or not self.launcher.policy.rejoins:
            return
        self.standby_pool = _HostPool(
            self.launcher, self.hub.worker_address, self.result_q, None,
            self.timeout, _rejoin_high_water(self.launcher.policy),
        )

    def _assign(self, wid: str, pool: _HostPool, use_barrier: bool) -> None:
        w = self.by_id[wid]
        self.procs[wid] = pool.assign(
            w,
            self.launcher.per_worker_hyperparams.get(wid, {}),
            self.launcher.program_overrides.get(w.role),
            use_barrier=use_barrier,
        )

    def spawn(self, wid: str) -> None:
        """Engine arrival directive: start the logical worker — lazily, at
        its arrival event, never earlier. Pooled: a task assignment to a
        warm host; classic: a dedicated process spawn."""
        if self.pool is not None:
            self._assign(wid, self.pool, use_barrier=wid in self.initial)
            return
        barrier = self.barrier if wid in self.initial else None
        ack = self.launcher._ctx.Event()
        self.drop_acks[wid] = ack
        self.procs[wid] = self.launcher._spawn(
            self.by_id[wid], self.hub.worker_address, barrier, self.result_q,
            self.timeout, drop_ack=ack,
        )

    def signal_rejoin(self, wid: str) -> None:
        pool = self.pool or self.standby_pool
        if pool is None:  # pragma: no cover - engine re-joins scheduled wids
            raise RuntimeError(f"no re-join standby pool for worker {wid!r}")
        self._assign(wid, pool, use_barrier=False)

    def _send_ack(self, wid: str) -> None:
        """Deliver the driver's drop acknowledgement to wherever the worker
        runs: its owning pool host's ack queue, or its dedicated process's
        event."""
        for pool in (self.pool, self.standby_pool):
            if pool is not None and pool.owns(wid):
                pool.ack(wid)
                return
        ack = self.drop_acks.get(wid)
        if ack is not None:
            ack.set()

    def kill(self, wid: str) -> None:
        """Engine kill directive for a dropped worker that will not re-join.
        Nothing to do eagerly: the directive arrives at the ``dropping``
        phase, while the process is still alive waiting for the drop ack and
        about to marshal its final state; it exits on its own after phase 2,
        and teardown (``close``) reaps any process that does not."""

    # ------------------------------ pump ------------------------------- #
    def start_pump(self) -> None:
        self._pump_thread = threading.Thread(
            target=self._pump, name="spawn-supervisor-pump", daemon=True
        )
        self._pump_thread.start()

    def stop_pump(self) -> None:
        self._stop.set()
        if self._pump_thread is not None:
            self._pump_thread.join(timeout=5.0)

    def _finish(self, wid: str, error: Optional[BaseException] = None) -> None:
        with self._lock:
            self.pending.discard(wid)
            if error is not None:
                self.errors.setdefault(wid, error)
        for pool in (self.pool, self.standby_pool):
            if pool is not None:
                pool.release(wid)
        self.done[wid].set()

    def _absorb(self, wid: str, status: str, payload: Any) -> None:
        role = self.by_id[wid].role
        if status == "ok":
            with self._lock:
                self.programs[wid] = _remote_program(wid, role, payload)
            self._finish(wid)
            return
        if status == "err":
            etype, emsg = payload
            self._finish(wid, error=RuntimeError(f"[{etype}] {emsg}"))
            return
        if status == "dropping":
            # phase 1: the worker announced its dropout and is parked on the
            # ack — record it and cascade orphans (hub-side poison) NOW,
            # before the worker leaves its channels, so no child ever sees
            # a limbo state (the ordering the engine documents)
            self._rejoin_at[wid] = self.engine.worker_dropped(wid, float(payload))
            self._send_ack(wid)
            return
        if status == "dropped":
            at, summary = payload
            # the dropped worker's thread/process is settling; free its pool
            # slot so a re-join assignment balances onto the emptiest host
            for pool in (self.pool, self.standby_pool):
                if pool is not None:
                    pool.release(wid)
            # keep the dropped worker's last state visible (the threaded
            # runtime keeps the dropped program object); a successful re-join
            # run overwrites it with the respawned worker's final state
            with self._lock:
                self.programs[wid] = _remote_program(wid, role, summary)
            # the directive was computed at the "dropping" phase; `rejoin`
            # resets the hub drop/clock state and restarts through the
            # handle (pre-warmed standby)
            rejoin_at = self._rejoin_at.pop(wid, None)
            if rejoin_at is None:
                self._finish(wid)
            else:
                try:
                    self.engine.rejoin(wid, rejoin_at)
                except BaseException as exc:  # noqa: BLE001
                    self._finish(wid, error=exc)
            return
        self._finish(wid, error=RuntimeError(f"unknown worker status {status!r}"))

    def _pump(self) -> None:
        while self.pending and not self._stop.is_set():
            remaining = self.deadline - time.monotonic()
            if remaining <= 0:
                break
            try:
                item = self.result_q.get(timeout=min(remaining, 0.25))
            except queue_mod.Empty:
                if self._check_crashed():
                    break
                continue
            self._absorb(*item)
        # final sweep for results still buffered in the queue's pipe
        while self.pending:
            try:
                item = self.result_q.get(timeout=0.5)
            except queue_mod.Empty:
                break
            self._absorb(*item)

    def _check_crashed(self) -> bool:
        """Fast-fail hardening: a worker process that died *without*
        reporting can never complete, and in a barriered cohort its peers
        would wait out the whole job timeout for it. Detect it, record the
        crash, and tear the remaining tree down. Returns True when the pump
        should stop."""
        dead = [
            wid for wid in list(self.pending)
            if (proc := self.procs.get(wid)) is not None and not proc.is_alive()
        ]
        if not dead:
            return False
        # one more poll: the result may still be in the pipe
        try:
            self._absorb(*self.result_q.get(timeout=0.5))
            return False
        except queue_mod.Empty:
            pass
        crashed = sorted(wid for wid in dead if wid in self.pending)
        if not crashed:
            return False
        for wid in crashed:
            self._finish(wid, error=RuntimeError(
                f"worker process {wid!r} exited without a result "
                f"(exitcode={self.procs[wid].exitcode})"
            ))
        for wid in sorted(self.pending):
            proc = self.procs.get(wid)
            if proc is not None and proc.is_alive():
                proc.terminate()
            self._finish(wid, error=RuntimeError(
                f"worker process {wid!r} torn down after peer crash: {crashed}"
            ))
        return True

    # ----------------------------- teardown ---------------------------- #
    def close(self) -> None:
        self._stop.set()
        if self._pump_thread is not None:
            self._pump_thread.join(timeout=5.0)
        if self.pool is None:
            # classic deployment: reap the dedicated worker processes. A
            # re-joined worker's entry points at its standby-pool host —
            # leave those to the pool close below, which sends the shutdown
            # sentinel first instead of burning the reap join timeout on a
            # host that is merely parked
            hosts = (
                {id(p) for p in self.standby_pool.procs()}
                if self.standby_pool is not None
                else set()
            )
            MultiprocLauncher._reap(
                [p for p in self.procs.values() if p is not None and id(p) not in hosts]
            )
        for pool in (self.pool, self.standby_pool):
            # an unused standby host is parked on its task queue and must
            # never receive a worker of a finished job — close() sends the
            # shutdown sentinel and reaps
            if pool is not None:
                pool.close()
        self.result_q.close()


class _ProcessWorkerHandle:
    """``WorkerHandle`` binding one engine worker to OS processes."""

    def __init__(self, supervisor: _ProcessSupervisor, worker: WorkerConfig) -> None:
        self._sup = supervisor
        self._wid = worker.worker_id

    def start(self, at: float) -> None:
        self._sup.spawn(self._wid)

    def restart(self, at: float) -> None:
        self._sup.signal_rejoin(self._wid)

    def kill(self, at: float) -> None:
        self._sup.kill(self._wid)

    def wait(self, timeout: float) -> bool:
        # once the supervisor's pump deadline has passed, nothing will ever
        # set this worker's done event — don't stack per-worker timeouts
        remaining = max(0.0, self._sup.deadline - time.monotonic()) + 1.0
        return self._sup.done[self._wid].wait(min(timeout, remaining))


def run_job_multiproc(
    job: JobSpec,
    registry: Optional[ResourceRegistry] = None,
    **kwargs: Any,
) -> JobResult:
    """One-call multiproc deployment, mirroring ``repro.core.runtime.run_job``."""
    timeout = float(kwargs.pop("timeout", 120.0))
    return MultiprocLauncher(job, registry, **kwargs).run(timeout=timeout)
