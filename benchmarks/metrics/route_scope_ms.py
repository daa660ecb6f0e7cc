"""expert routing: device self time of the ops under the phase ``moe_route``
(router product, top-k, sort by expert, gather of the routed rows, combine),
median over the traced runs of the kind of step the window ran most, in
milliseconds. Nothing where the program has no such phase."""
LAYER = "expert routing"
MOVES = "samples_per_s"


def read(run):
    return run["phase_median_ms"](("moe_route",))
