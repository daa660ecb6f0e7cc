"""capture: device self time of the ops under the phase ``kfac_capture`` (the
factor products and their running averages), median over the traced runs of
the kind the window ran most among those that capture, in milliseconds."""
LAYER = "capture"
MOVES = "samples_per_s"


def read(run):
    return run["phase_median_ms"](("kfac_capture",))
