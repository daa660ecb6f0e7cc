"""apply: what preconditioning adds to every step on the device: median
device time of the traced ``plain`` steps minus that of the SGD twin's, from
the trace's program runs."""
import statistics

LAYER = "apply"
MOVES = "samples_per_s"


def read(run):
    ms = run["device_ms"]
    if "plain" not in ms or "twin" not in ms:
        return None
    return statistics.median(ms["plain"]) - statistics.median(ms["twin"])
