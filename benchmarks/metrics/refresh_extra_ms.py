"""refresh: what a refresh adds on the device: median device time of the
traced ``refresh`` steps minus that of the step kind it replaces
(``factors``), from the trace's program runs. One reader for both of the
quantity's names: ``refresh_extra_ms.tail`` in cells where refresh steps are
5% of steps or more (they are the tail), ``refresh_extra_ms.rare`` where they
are fewer (they cost throughput; no cell yet); ``BENCHMARK.json`` says which
cell reports which."""
import statistics

LAYER = "refresh"
MOVES = {"refresh_extra_ms.tail": "step_p95_ms", "refresh_extra_ms.rare": "samples_per_s"}


def read(run):
    ms = run["device_ms"]
    if "refresh" not in ms or "factors" not in ms:
        return None
    return statistics.median(ms["refresh"]) - statistics.median(ms["factors"])
