"""capture: the least time the chip could take for one capture step's factor
products (``work/``: 2 x rows x side^2 per factor as full products against
the bf16 peak, their bytes against the HBM peak) over ``capture_scope_ms``,
in percent."""
LAYER = "capture"
MOVES = "samples_per_s"


def read(run):
    ms = run["read"]("capture_scope_ms")
    if not ms:
        return None
    least, _bound = run["least_seconds"](run["work"]["capture"])
    return 100.0 * least * 1e3 / ms
