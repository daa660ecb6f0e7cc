"""step builder: median step time of the plain-SGD twin (same model, same
optimizer, ``kfac=None``), run after the window in the traced run."""
import statistics

LAYER = "step builder"
MOVES = "samples_per_s"


def read(run):
    return statistics.median(run["twin_ms"]) if run["twin_ms"] else None
