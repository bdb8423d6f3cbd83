"""The weighted fold under every aggregator.

One mechanism computes every sample-weighted mean of client updates: each
update is scaled by its weight, added into a running accumulator in the
caller's order, and the sum is divided once, on the host, by the total.
``StreamingMean`` runs it one update at a time; ``weighted_mean`` runs it
over a list; the buffered async strategies fold through the same jitted
``scale``/``add`` pair. One rule, ``device_fold``, decides whether a fold
runs on the device or in numpy. Both give the same bits, so the rule decides
speed only. ``_fold_allreduce`` is gossip's f64 consensus fold.
"""
from __future__ import annotations

from typing import Any, Optional, Sequence, Tuple

import jax
import numpy as np

from repro.core.spans import FOLD_ADD, FOLD_FINALIZE, FOLD_PARTIAL, FOLD_SCALE, span

# payloads at least this many elements fold on the device; below it the
# per-leaf numpy ops win on dispatch overhead
FUSED_AGG_MIN_ELEMS = 16_384


@jax.jit
def scale(x: jax.Array, w: jax.Array) -> jax.Array:
    # own jit entry: compiling the scale together with the add would allow
    # FMA contraction and break bit-equality with the eager ``a + w*d`` chain
    return x * w


@jax.jit
def add(a: jax.Array, s: jax.Array) -> jax.Array:
    return a + s


def device_fold(tree: Any) -> bool:
    """Whether ``tree`` folds through the jitted pair: not on the CPU
    backend, where the numpy ops are already the fast path, and only for at
    least ``FUSED_AGG_MIN_ELEMS`` elements summed over its leaves."""
    if jax.default_backend() == "cpu":
        return False
    elems = sum(int(np.size(leaf)) for leaf in jax.tree_util.tree_leaves(tree))
    return elems >= FUSED_AGG_MIN_ELEMS


def _device_free_bytes() -> Optional[int]:
    """Bytes free on the device jitted calls run on, or ``None`` where it
    does not say (the CPU)."""
    stats = jax.local_devices()[0].memory_stats()
    if not stats or "bytes_limit" not in stats:
        return None
    return int(stats["bytes_limit"]) - int(stats.get("bytes_in_use", 0))


class StreamingMean:
    """O(1)-memory sample-weighted mean of client model pytrees.

    ``fold(weights, n)`` absorbs one client update at a time — callers feed
    updates in sorted-src order — and ``finalize()`` returns
    ``(mean_tree, total_samples)`` (``(None, 0.0)`` when nothing carried
    positive weight). Only the running accumulator tree is retained: the
    peak number of client update trees held at once is 1 regardless of
    client count (``peak_buffered``).

    Bit-identity: each update is scaled, then added, then the sum divided
    once by the Python-float total on the host. ``fused`` (None: ask
    ``device_fold``) routes the scale and the add through the jitted
    ``scale``/``add`` pair, two jit entries so nothing is FMA-contracted;
    the numpy path runs the same IEEE ops, so for the same fold order both
    give the same bytes.

    On the fused path the accumulator stays on the device between folds and
    is pulled to numpy once, when it is read (``finalize``, ``partial``,
    ``fold_partial``), where the device has room for it beside a scaled
    update: ``2·N`` plus two of its largest leaves free at the first fold,
    for updates of ``N`` bytes. Without that room each sum goes back to the
    host as it is made, and the device holds about ``N`` plus two leaves.
    ``h2d_bytes`` counts the numpy leaves handed to a jitted call, and
    ``d2h_bytes`` the device leaves turned back into numpy: for a round of
    ``C`` updates ``C·N`` in and ``N`` out with the accumulator kept on the
    device, ``(2·C − 1)·N`` in and ``C·N`` out without; 0 on the host path.
    """

    def __init__(self, fused: Optional[bool] = None) -> None:
        self._fused = fused
        self._acc: Any = None
        self._resident: Optional[bool] = None
        self._total = 0.0
        self.count = 0
        self.peak_buffered = 0
        self.h2d_bytes = 0
        self.d2h_bytes = 0

    def _resolve_fused(self, weights: Any) -> bool:
        if self._fused is None:
            self._fused = device_fold(weights)
        return bool(self._fused)

    def fold(self, weights: Any, n: float) -> None:
        n = float(n)
        self._total += n
        self.count += 1
        self.peak_buffered = max(self.peak_buffered, 1)
        if self._resolve_fused(weights):
            w = np.float32(n)
            settle = (lambda x: x) if self._keeps_acc(weights) else self._to_host
            with span(FOLD_SCALE):
                leaves, treedef = jax.tree_util.tree_flatten(weights)
                scaled = [scale(self._to_device(x), w) for x in leaves]
            with span(FOLD_ADD):
                if self._acc is None:
                    acc = [settle(s) for s in scaled]
                else:
                    acc = treedef.flatten_up_to(self._acc)
                    for a, s in zip(acc, scaled):
                        if np.shape(a) != s.shape:  # as numpy's add refuses it
                            raise ValueError(
                                f"update leaf of shape {s.shape} does not match "
                                f"the accumulator's {np.shape(a)}"
                            )
                    self._acc = None  # each old leaf is freed as its sum is made
                    for i, s in enumerate(scaled):
                        a = acc[i]
                        if not isinstance(a, jax.Array):  # a host sum goes in
                            a = self._to_device(a)
                        acc[i] = settle(add(a, s))
                self._acc = treedef.unflatten(acc)
            return
        with span(FOLD_SCALE):
            scaled = jax.tree_util.tree_map(lambda x: np.asarray(x) * n, weights)
        with span(FOLD_ADD):
            if self._acc is None:
                self._acc = scaled
            else:
                self._acc = jax.tree_util.tree_map(np.add, self._acc, scaled)

    def _keeps_acc(self, weights: Any) -> bool:
        """Whether the fused accumulator stays on the device: decided at the
        first fold, from the room the device has for it and a scaled update
        (``2·N`` plus two of the largest leaves)."""
        if self._resident is None:
            sizes = [np.asarray(x).nbytes for x in jax.tree_util.tree_leaves(weights)]
            free = _device_free_bytes()
            self._resident = free is None or 2 * sum(sizes) + 2 * max(sizes, default=0) <= free
        return self._resident

    def _to_device(self, x: Any) -> np.ndarray:
        """``x`` as the numpy leaf a jitted call copies in, counted."""
        x = np.asarray(x)
        self.h2d_bytes += x.nbytes
        return x

    def _to_host(self, x: Any) -> np.ndarray:
        """A jitted call's result pulled into numpy, counted."""
        x = np.asarray(x)
        self.d2h_bytes += x.nbytes
        return x

    def _host_acc(self) -> Any:
        """The accumulator as numpy; device leaves are pulled once."""
        self._acc = jax.tree_util.tree_map(
            lambda x: self._to_host(x) if isinstance(x, jax.Array) else x, self._acc
        )
        return self._acc

    def partial(self) -> Tuple[Optional[Any], float]:
        """The raw running state: ``(weighted_sum_tree, total_weight)``.

        This is the reduce plane's shard partial — unfinalized on purpose,
        so a downstream fold over several partials can divide once by the
        grand total exactly like :meth:`finalize` does, keeping the
        one-shard case bit-identical to the per-frame streaming fold. The
        tree is numpy: the hub packs it for the wire."""
        return self._host_acc(), self._total

    def fold_partial(self, acc: Any, total: float, count: int = 1) -> None:
        """Absorb another accumulator's raw ``(acc, total)`` partial.

        Partials are pre-scaled sums, so folding is a plain tree add (no
        re-scaling); callers feed partials in sorted-shard order. ``count``
        carries the number of source updates inside the partial so
        ``self.count`` keeps meaning "updates folded"."""
        if acc is None or count <= 0:
            return
        self._total += float(total)
        self.count += int(count)
        self.peak_buffered = max(self.peak_buffered, 1)
        with span(FOLD_PARTIAL):
            if self._acc is None:
                self._acc = jax.tree_util.tree_map(np.asarray, acc)
            else:
                self._acc = jax.tree_util.tree_map(np.add, self._host_acc(), acc)

    def finalize(self) -> Tuple[Optional[Any], float]:
        if self._acc is None or self._total <= 0:
            return None, 0.0
        with span(FOLD_FINALIZE):
            # f32 division on the v5e is not correctly rounded: divide on the host
            mean = jax.tree_util.tree_map(lambda x: x / self._total, self._host_acc())
        return mean, self._total


def weighted_mean(
    updates: Sequence[Tuple[Any, float]],
    *,
    fused: Optional[bool] = None,
) -> Tuple[Optional[Any], float]:
    """Sample-weighted mean of ``(tree, num_samples)`` updates, folded in the
    list's order by a ``StreamingMean``. Returns ``(mean_tree,
    total_samples)``; ``(None, 0.0)`` when no update carries positive
    weight."""
    acc = StreamingMean(fused=fused)
    for weights, n in updates:
        acc.fold(weights, n)
    return acc.finalize()


def _fold_allreduce(
    me: str,
    own_weights: Any,
    own_samples: float,
    received: Sequence[Tuple[str, Any]],
) -> Tuple[Any, int]:
    """Sample-weighted mean of own + received models, folded in sorted
    worker-id order so every ring member — on any transport backend, whatever
    the arrival order — accumulates in the same sequence and lands on
    byte-identical consensus weights."""
    contributions = sorted(
        [(me, {"weights": own_weights, "num_samples": own_samples})]
        + list(received),
        key=lambda t: t[0],
    )
    total = 0.0
    acc = None
    for _, msg in contributions:
        n = float(msg.get("num_samples", 1))
        total += n
        scaled = jax.tree_util.tree_map(
            lambda x: np.asarray(x, dtype=np.float64) * n, msg["weights"]
        )
        acc = scaled if acc is None else jax.tree_util.tree_map(np.add, acc, scaled)
    mean = jax.tree_util.tree_map(
        lambda a: (a / total).astype(np.float32), acc
    )
    return mean, int(total)
