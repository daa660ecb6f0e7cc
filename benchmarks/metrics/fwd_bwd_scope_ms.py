"""step builder: device self time of the ops under the phase ``model`` (the
forward and backward passes), median over the traced runs of the kind of step
the window ran most (``trace_phases.median_ms``), in milliseconds."""
LAYER = "step builder"
MOVES = "samples_per_s"


def read(run):
    return run["phase_median_ms"](("model",))
