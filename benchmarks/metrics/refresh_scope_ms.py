"""refresh: device self time of the ops under the phase ``kfac_refresh`` (the
inverses of the factors), median over the traced ``refresh`` runs, in
milliseconds. The ops the compiler's own expansions make without a name are
not in it (``unscoped_pct``; PERF.md, section 5)."""
LAYER = "refresh"
MOVES = "step_p95_ms"


def read(run):
    return run["phase_median_ms"](("kfac_refresh",), kind="refresh")
