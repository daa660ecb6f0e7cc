"""input: the host's span round ``put_global_batch``, mean per step of the
window, in milliseconds (host clock, the benchmark's own loop)."""
LAYER = "input"
MOVES = "samples_per_s"


def read(run):
    puts = [r["put_ms"] for r in run["records"]]
    return sum(puts) / len(puts) if puts else None
