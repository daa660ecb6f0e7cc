"""step builder: device self time under the phases ``optimizer`` and
``grad_clip`` together (the clip's scaling fuses into the optimizer's pass, so
the two cannot be told apart: PERF.md, section 6, PR 25), median over the
traced runs of the kind of step the window ran most, in milliseconds."""
LAYER = "step builder"
MOVES = "samples_per_s"


def read(run):
    return run["phase_median_ms"](("optimizer", "grad_clip"))
