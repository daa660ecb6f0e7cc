"""whole step: the share of a run's device time in ops that carry no phase
(copies and expansions the compiler makes itself): the blind share of the
``*_scope_*`` lines. Median over the traced runs of the kind of step the
window ran most, in percent."""
LAYER = "whole step"
MOVES = "samples_per_s"


def read(run):
    return run["phase_median_ms"](("unscoped",), share=True)
