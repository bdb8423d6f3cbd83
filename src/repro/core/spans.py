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

Inside a jitted step the device's work is named by scopes instead: a
``jax.named_scope`` puts its name into the op name of every operation
traced under it, which the compiled program keeps as metadata. The model
opens these once per layer:

======================  ==========================================================
``attn/proj``           q/k/v projections, the q/k norm, RoPE, the output projection
``attn/core``           scores, causal mask, softmax and the weighted values
``moe/route``           router logits, softmax, top-k and the load-balancing loss
``moe/dispatch``        picks sorted by held expert, their tokens gathered
``moe/experts``         the held experts' grouped matrix products
``moe/combine``         expert outputs weighted and added back to their tokens
``lm/ce``               the output head and the cross-entropy
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

ATTN_PROJ = "attn/proj"
ATTN_CORE = "attn/core"
MOE_ROUTE = "moe/route"
MOE_DISPATCH = "moe/dispatch"
MOE_EXPERTS = "moe/experts"
MOE_COMBINE = "moe/combine"
LM_CE = "lm/ce"
SCOPES = (ATTN_PROJ, ATTN_CORE, MOE_ROUTE, MOE_DISPATCH, MOE_EXPERTS,
          MOE_COMBINE, LM_CE)


def role_span(role: str, what: str) -> str:
    """``<role>/<what>``: the name of a span on ``role``'s thread."""
    return f"{role}/{what}"


def span(name: str) -> jax.profiler.TraceAnnotation:
    """The host span ``name``, entered with ``with span(name): ...``."""
    return jax.profiler.TraceAnnotation(name)


def scope(name: str):
    """The device scope ``name``, entered with ``with scope(name): ...``
    while a jitted function traces."""
    return jax.named_scope(name)
