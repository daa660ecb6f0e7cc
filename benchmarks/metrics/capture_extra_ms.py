"""capture: what a capture step adds on the device: median device time of
the traced ``factors`` steps minus that of the ``plain`` steps, from the
trace's program runs."""
import statistics

LAYER = "capture"
MOVES = "samples_per_s"


def read(run):
    ms = run["device_ms"]
    if "factors" not in ms or "plain" not in ms:
        return None
    return statistics.median(ms["factors"]) - statistics.median(ms["plain"])
