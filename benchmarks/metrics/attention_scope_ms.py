"""attention: device self time of the ops under the phase ``attention`` (the
attention kernel forward and backward with the rotary and latent reshapes
round it), median over the traced runs of the kind of step the window ran
most, in milliseconds."""
LAYER = "attention"
MOVES = "samples_per_s"


def read(run):
    return run["phase_median_ms"](("attention",))
