"""apply: device self time of the ops under the phase ``kfac_apply``
(preconditioning every layer's gradient, the KL clip, the write-back), median
over the traced runs of the kind of step the window ran most, in
milliseconds."""
LAYER = "apply"
MOVES = "samples_per_s"


def read(run):
    return run["phase_median_ms"](("kfac_apply",))
