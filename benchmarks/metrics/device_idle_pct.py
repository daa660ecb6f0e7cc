"""device: 1 - (union of device-op intervals over the traced window), in
percent, averaged over the chips used (device trace)."""
LAYER = "device"
MOVES = "samples_per_s"


def read(run):
    t = run["trace"]
    if not t or not t["window_s"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
