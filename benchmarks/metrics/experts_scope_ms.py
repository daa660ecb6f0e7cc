"""expert products: device self time of the ops under the phase
``moe_experts`` (the grouped products over the held experts' rows, forward,
recomputed forward and backward), median over the traced runs of the kind of
step the window ran most, in milliseconds."""
LAYER = "expert products"
MOVES = "samples_per_s"


def read(run):
    return run["phase_median_ms"](("moe_experts",))
