"""The phases of a training step, named once for both clocks.

``with phase("kfac_capture"):`` enters ``jax.named_scope("kfac_capture")``,
so every op traced inside carries the name as a component of its op-name
path (``jit(train_step)/model/jvp(TransformerLM)/block_0/qkv/kfac_capture/
dot_general``): HLO metadata only, the compiled program computes the same
thing at the same cost. A device trace is split by phase from those paths
(:mod:`.device_phases`; the innermost phase component of a path wins, which
is how the A products that the model's forward pass sows count as capture
and not as model). Given a span name, the same ``with`` also enters that
telemetry span (host clock, trace time; a no-op while telemetry is off), so
one call site marks one phase on both clocks.

The scopes are always there: no switch turns them on or off.
"""

from __future__ import annotations

import contextlib
from typing import Optional

import jax

from kfac_pytorch_tpu.observability.telemetry import get_telemetry

# Names are single path components (a slash would split into two).
PHASES = (
    "model",  # forward and backward of the loss: the value_and_grad call
    "grad_clip",  # global-norm clip between gradient averaging and K-FAC
    "kfac_capture",  # factor products (A, G) and their running averages
    "kfac_exchange",  # factor reductions across devices, eigen exchange
    "kfac_refresh",  # inverses / eigendecompositions of the factors
    "kfac_apply",  # precondition every layer's gradient, KL clip
    "optimizer",  # the SGD tail: momentum, weight decay, parameter step
    # entered in models/glm_moe_lite.py alone, inside "model" (the innermost
    # phase of an op's path wins): in a model without them "model" is the whole
    # forward and backward, with them it is the model less these three
    "attention",  # the attention call with the rotary and latent reshapes round it
    "moe_route",  # router product, top-k, sort by expert, gather, combine
    "moe_experts",  # the grouped products over the held experts' rows
)


@contextlib.contextmanager
def phase(name: str, span: Optional[str] = None):
    """Enter the named scope of one of :data:`PHASES` and, where ``span``
    names one, the trace-time telemetry span registered for this call site
    (docs/OBSERVABILITY.md). Usable as a decorator too."""
    if name not in PHASES:
        raise ValueError(f"phase {name!r} is not one of {PHASES}")
    with jax.named_scope(name):
        if span is None:
            yield
        else:
            with get_telemetry().span(span):
                yield
