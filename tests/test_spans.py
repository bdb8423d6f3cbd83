"""The program's host spans and the streaming fold's copy counters.

A classical-FL job (3 trainers, 2 rounds, small float32 updates, the fused
fold forced on) runs under the JAX profiler on the CPU; its trace must hold
every span of ``repro.core.spans`` under its exact name, and the fold's
per-round byte counters must match what the fused path copies.
"""
import jax
import numpy as np
import pytest

from repro.core import spans
from repro.core.expansion import JobSpec
from repro.core.runtime import run_job
from repro.core.tag import DatasetSpec
from repro.core.topologies import classical_fl

TRAINERS, ROUNDS = 3, 2
W0 = {
    "w": (0.01 * np.random.default_rng(7).normal(size=(32, 10))).astype(np.float32),
    "b": np.zeros((10,), np.float32),
}
N = sum(x.nbytes for x in W0.values())
# the on-chip benchmark opens these itself and reads them by exact name
BENCHMARK_SPANS = {"window", "aggregate", "upload", "fetch"}
TASKLETS = {
    "trainer": ("load", "init", "fetch", "train", "evaluate", "upload"),
    "global-aggregator": ("init", "distribute", "aggregate", "evaluate",
                          "check_rounds", "end_of_train"),
}


def _job(**hyperparams):
    return JobSpec(
        tag=classical_fl(
            trainer_program="repro.transport.conformance.SeededSGDTrainer"
        ),
        datasets=tuple(DatasetSpec(name=f"d{i}") for i in range(TRAINERS)),
        hyperparams={"rounds": ROUNDS, "init_weights": W0, **hyperparams},
    )


def _traced(logdir, job):
    """Run ``job`` under the profiler: the result, and the host spans as
    ``(name, start_ns, end_ns)``."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(logdir), profiler_options=opts)
    try:
        res = run_job(job, timeout=60)
    finally:
        jax.profiler.stop_trace()
    assert not res.errors, res.errors
    (path,) = logdir.glob("**/*.xplane.pb")
    data = jax.profiler.ProfileData.from_file(str(path))
    events = [
        (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
        for plane in data.planes if not plane.name.startswith("/device:")
        for line in plane.lines for ev in line.events
    ]
    return res, events


@pytest.fixture(scope="module")
def fused(tmp_path_factory):
    return _traced(tmp_path_factory.mktemp("fused"), _job(fused_aggregation=True))


@pytest.fixture(scope="module")
def reduced(tmp_path_factory):
    """The hub-reduced incast: the server folds one partial per round."""
    return _traced(tmp_path_factory.mktemp("reduced"), _job(reduce_plan=1))


def _named(events, name):
    return [(lo, hi) for n, lo, hi in events if n == name]


def _agg_metrics(res):
    return [m for m in res.program("global-aggregator-0").metrics if "agg_folds" in m]


def test_every_span_appears_under_its_exact_name(fused, reduced):
    names = {n for n, _, _ in fused[1] + reduced[1]}
    want = {spans.FOLD_SCALE, spans.FOLD_ADD, spans.FOLD_PARTIAL, spans.FOLD_FINALIZE}
    for role, aliases in TASKLETS.items():
        want |= {spans.role_span(role, a) for a in aliases}
        want |= {spans.role_span(role, spans.RECV), spans.role_span(role, spans.SEND)}
    assert want <= names, sorted(want - names)


def test_fold_spans_and_server_receives_are_one_per_update(fused):
    _, events = fused
    per_update = TRAINERS * ROUNDS
    assert len(_named(events, spans.FOLD_SCALE)) == per_update
    assert len(_named(events, spans.FOLD_ADD)) == per_update
    assert len(_named(events, "global-aggregator/recv")) == per_update
    assert len(_named(events, spans.FOLD_FINALIZE)) == ROUNDS
    assert len(_named(events, "global-aggregator/aggregate")) == ROUNDS


def test_fold_spans_lie_inside_the_aggregate_tasklet(fused, reduced):
    # a hub-reduced incast folds each update on its sender's thread as it
    # arrives, so there only the server's own fold spans are checked
    server_folds = {spans.FOLD_PARTIAL, spans.FOLD_FINALIZE}
    for (_, events), names in ((fused, None), (reduced, server_folds)):
        aggregate = _named(events, "global-aggregator/aggregate")
        folds = [(lo, hi) for n, lo, hi in events if n.startswith("fold/")
                 and (names is None or n in names)]
        assert folds
        for lo, hi in folds:
            assert any(a <= lo and hi <= b for a, b in aggregate), (lo, hi)


def test_a_receive_span_closes_before_the_fold_of_its_frame(fused):
    _, events = fused
    folds = [(lo, hi) for n, lo, hi in events if n.startswith("fold/")]
    for lo, hi in _named(events, "global-aggregator/recv"):
        assert not any(a < hi and lo < b for a, b in folds), (lo, hi)


def test_no_program_span_takes_a_benchmark_name(fused, reduced):
    for _, events in (fused, reduced):
        assert not {n for n, _, _ in events} & BENCHMARK_SPANS


@pytest.mark.parametrize("fused_aggregation", [True, False])
def test_copy_counters_count_what_the_fold_moves(fused_aggregation):
    res = run_job(_job(fused_aggregation=fused_aggregation), timeout=60)
    assert not res.errors, res.errors
    rounds = _agg_metrics(res)
    assert len(rounds) == ROUNDS
    for m in rounds:
        assert m["agg_folds"] == TRAINERS
        if fused_aggregation:
            # each update in; the accumulator out once, at finalize
            assert m["h2d_bytes"] == TRAINERS * N
            assert m["d2h_bytes"] == N
            assert m["h2d_bytes"] + m["d2h_bytes"] == (TRAINERS + 1) * N
        else:
            assert m["h2d_bytes"] == m["d2h_bytes"] == 0


def test_tracing_leaves_the_weights_bit_identical(fused):
    traced = fused[0].global_weights()
    plain = run_job(_job(fused_aggregation=True), timeout=60)
    assert not plain.errors, plain.errors
    for k in W0:
        assert np.asarray(traced[k]).tobytes() == np.asarray(plain.global_weights()[k]).tobytes()
