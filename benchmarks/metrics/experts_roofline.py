"""expert products: the least time the chip could take for one step's grouped
products at the rows routed (``work/``: forward and both gradients of the
three projections at the held experts' mean load against the bf16 peak, the
banks' and the rows' bytes against the HBM peak) over ``experts_scope_ms``,
in percent."""
LAYER = "expert products"
MOVES = "samples_per_s"


def read(run):
    ms = run["read"]("experts_scope_ms")
    if not ms or "experts" not in run["work"]:
        return None
    least, _bound = run["least_seconds"](run["work"]["experts"])
    return 100.0 * least * 1e3 / ms
