"""step builder: median time of ``plain`` steps (no capture, no refresh):
the window's own where its schedule has them, else the steps of the plain
program run after the window (traced run only)."""
import statistics

LAYER = "step builder"
MOVES = "samples_per_s"


def read(run):
    ms = [r["ms"] for r in run["records"] if r["kind"] == "plain"] or run["plain_after_ms"]
    return statistics.median(ms) if ms else None
