"""expert routing: the largest held expert's rows over the held experts' mean,
in the worst layer of a step (the program's step scalar
``moe_load_max_over_mean``), median over the window's steps. Nothing where
the program emits no such counter."""
import statistics

LAYER = "expert routing"
MOVES = "samples_per_s"


def read(run):
    values = [r["counters"]["moe_load_max_over_mean"] for r in run["records"]
              if "moe_load_max_over_mean" in r.get("counters", {})]
    return statistics.median(values) if values else None
