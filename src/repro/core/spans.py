"""Named host spans of the round's work, in the JAX profiler's trace.

A span is a ``jax.profiler.TraceAnnotation``: a no-op unless a trace is
running, and on the device planes' clock when one is. Each name is built
once, where the chain, channel end or fold is made, never per call. The
trace keeps a worker thread's line under no useful name, so a span taken on
a role's thread carries the role in its name.

======================  ==========================================================
``<role>/<alias>``      one tasklet of the role's chain (``Tasklet.run``)
``<role>/recv``         the wait for, and the take of, one frame
``<role>/send``         handing a payload to the backend
``fold/scale``          ``StreamingMean.fold``: the update, scaled
``fold/add``            ``StreamingMean.fold``: added to the accumulator
``fold/partial``        ``StreamingMean.fold_partial``: a hub partial added
``fold/finalize``       ``StreamingMean.finalize``: the pull and the host division
======================  ==========================================================
"""
from __future__ import annotations

import jax

RECV = "recv"
SEND = "send"
FOLD_SCALE = "fold/scale"
FOLD_ADD = "fold/add"
FOLD_PARTIAL = "fold/partial"
FOLD_FINALIZE = "fold/finalize"


def role_span(role: str, what: str) -> str:
    """``<role>/<what>``: the name of a span on ``role``'s thread."""
    return f"{role}/{what}"


def span(name: str) -> jax.profiler.TraceAnnotation:
    """The host span ``name``, entered with ``with span(name): ...``."""
    return jax.profiler.TraceAnnotation(name)
