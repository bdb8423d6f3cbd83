"""Cells of kind ``tag_round``: sync rounds of a classical-FL TAG job on
the ``inproc`` backend, threads for workers.

Each trainer's update is one decoder layer's leaves (shapes from the
program's model, through ``jax.eval_shape``), made on the device from the
seed in set-up and copied to the host. Every round, trainer ``i`` uploads
its own update with a sample count drawn from (seed, i, round), so a round
is the transport plus the aggregator's fold. The aggregator and trainers are
subclasses of the stock programs, passed through ``run_job``'s
``program_overrides``: they add the benchmark's spans and stop the job at
the first round that ends after ``--seconds``.

``correct`` compares the aggregate of sampled rounds with the sequential
numpy fold of the same updates, bit for bit: the last round the window
completed, and ``sample_picks`` rounds drawn from the seed among the others
it completed (a reservoir, so the draw is uniform over however many there
were).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import counts, foldref, generate, weights
from chipbench.harness import Outcome, memory_peak, profile


def model_config(cfg: dict):
    """The program's ``ModelConfig`` for a configuration file: every size
    the file states, set explicitly."""
    from repro.configs import get_config

    heads = cfg["num_attention_heads"]
    mc = dataclasses.replace(
        get_config(cfg["arch"]),
        num_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"],
        num_heads=heads,
        num_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["hidden_size"] // heads,
        d_ff=cfg["intermediate_size"],
        vocab_size=cfg["vocab_size"],
        rope_theta=float(cfg["rope_theta"]),
        norm_eps=float(cfg["rms_norm_eps"]),
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        param_dtype=cfg["torch_dtype"],
    )
    stated = {"qkv_bias": True, "activation": "swiglu", "family": "dense",
              "rope_type": "rope", "sliding_window": 0}
    for k, v in stated.items():
        if getattr(mc, k) != v:
            raise ValueError(f"{cfg['arch']}: program has {k}={getattr(mc, k)!r}, "
                             f"the configuration states {v!r}")
    return mc


def layer_shapes(cfg: dict):
    """One decoder layer's leaves as f32 shapes, from the program's model."""
    from repro.models.api import build_model

    one = dataclasses.replace(model_config(cfg), num_layers=1, scan_layers=True)
    shapes = jax.eval_shape(build_model(one).init, jax.random.key(0))
    (layer,) = shapes["groups"]
    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape[1:], jnp.float32), layer)


@dataclasses.dataclass
class Rounds:
    """Shared by the job's programs: the updates, the clock, the rounds
    held for the comparison."""

    updates: List[Any]
    seed: int
    samples: tuple
    seconds: float
    picks: int
    t_start: Optional[float] = None
    t_end: Optional[float] = None
    agg_s: List[float] = dataclasses.field(default_factory=list)
    reservoir: List[tuple] = dataclasses.field(default_factory=list)
    last: Optional[tuple] = None
    order: Dict[str, int] = dataclasses.field(default_factory=dict)
    _window: Any = None

    def count(self, client: int, round_: int) -> int:
        return generate.sample_count(self.seed, client, round_, *self.samples)

    def complete(self, round_: int, result: tuple) -> None:
        """Round ``round_`` ended with ``result`` (aggregate, sample total):
        it is held as the last round, and the round it follows goes through
        the reservoir of ``picks`` rounds drawn from the seed."""
        if self.last is not None:
            i = self.last[0]
            if i < self.picks:
                self.reservoir.append(self.last)
            else:
                slot = generate.reservoir_slot(self.seed, i)
                if slot < self.picks:
                    self.reservoir[slot] = self.last
        self.last = (round_, result)

    @property
    def kept(self) -> Dict[int, tuple]:
        """The compared rounds: the reservoir's and the last, by round."""
        held = self.reservoir + ([self.last] if self.last is not None else [])
        return dict(held)


def programs(r: Rounds):
    from repro.core.roles import GlobalAggregator, Trainer

    class BenchTrainer(Trainer):
        def load_data(self) -> None:
            self.index = int(self.ctx.worker.dataset[1:])
            r.order[self.ctx.worker.worker_id] = self.index
            self.trained = 0

        def fetch(self) -> None:
            with jax.profiler.TraceAnnotation("fetch"):
                super().fetch()

        def train(self) -> None:
            if self._work_done:
                return
            self.weights = r.updates[self.index]
            self.num_samples = r.count(self.index, self.trained)
            self.trained += 1

        def upload(self) -> None:
            with jax.profiler.TraceAnnotation("upload"):
                super().upload()

    class BenchAggregator(GlobalAggregator):
        def distribute(self) -> None:
            if r.t_start is None:
                r._window = jax.profiler.TraceAnnotation("window")
                r._window.__enter__()
                r.t_start = time.perf_counter()
            super().distribute()

        def aggregate(self) -> None:
            t = time.perf_counter()
            with jax.profiler.TraceAnnotation("aggregate"):
                super().aggregate()
            r.agg_s.append(time.perf_counter() - t)
            if not self._work_done:
                r.complete(self._round, (self.weights, self.agg_samples))

        def check_rounds(self) -> None:
            self._round += 1
            if time.perf_counter() - r.t_start >= r.seconds:
                self._work_done = True
                r.t_end = time.perf_counter()
                r._window.__exit__(None, None, None)

    return {"trainer": BenchTrainer, "global-aggregator": BenchAggregator}


def run_rounds(r: Rounds, cfg: dict, traffic: dict) -> None:
    from repro.core.expansion import JobSpec
    from repro.core.runtime import run_job
    from repro.core.tag import DatasetSpec
    from repro.core.topologies import classical_fl

    job = JobSpec(
        tag=classical_fl(backend=cfg["backend"]),
        datasets=tuple(DatasetSpec(name=f"d{i}") for i in range(traffic["trainers"])),
        hyperparams={"rounds": 1 << 30, "init_weights": r.updates[0]},
    )
    res = run_job(job, program_overrides=programs(r), timeout=r.seconds + 300)
    if res.errors:
        raise RuntimeError(f"TAG job failed: {res.errors}")


def reference(r: Rounds, bfloat16: bool = False) -> Dict[int, tuple]:
    """The sequential fold of every kept round, in worker order."""
    order = [r.order[w] for w in sorted(r.order)]
    return {
        rnd: foldref.sequential_fold(
            [(r.updates[i], float(r.count(i, rnd))) for i in order], bfloat16)
        for rnd in r.kept
    }


def mismatched(r: Rounds, want: Dict[int, tuple]) -> int:
    n = 0
    for rnd, (got, total) in r.kept.items():
        ref_mean, ref_total = want[rnd]
        n += foldref.mismatched(got, ref_mean) + int(total != ref_total)
    return n


def make_rounds(cell, seed: int, seconds: float) -> Rounds:
    tr = cell.traffic
    shapes = layer_shapes(cell.config)
    made = weights.updates_fn(shapes, tr["trainers"])(weights.seed_key(seed))
    updates = [jax.tree_util.tree_map(np.asarray, t) for t in jax.device_get(made)]
    del made
    return Rounds(updates, seed, tuple(tr["samples"]), seconds, tr["sample_picks"])


def run(cell, seed, seconds, trace, devices, t0) -> Outcome:
    tr = cell.traffic
    r = make_rounds(cell, seed, seconds)
    # warm-up: one whole round compiles every program the window runs
    warm = dataclasses.replace(r, seconds=0.0, picks=0, agg_s=[], reservoir=[],
                               last=None, order={})
    run_rounds(warm, cell.config, tr)
    del warm
    setup_s = time.perf_counter() - t0
    with profile(trace) as traced:
        run_rounds(r, cell.config, tr)
    peak = memory_peak(devices)
    rounds = len(r.agg_s)
    want = reference(r)
    elems = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(r.updates[0]))
    return Outcome(
        metrics={"setup_s": setup_s, "round_s": (r.t_end - r.t_start) / rounds},
        attempted=rounds, failed=0,
        numbers={"mismatched": float(mismatched(r, want))},
        counters={"rounds": rounds, "agg_s": list(r.agg_s),
                  "least_bytes": counts.fold_least_bytes(tr["trainers"], elems),
                  "rounds_compared": len(r.kept)},
        memory_peak_bytes=peak,
        trace=traced[0] if traced else None,
    )
