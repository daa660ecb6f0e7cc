"""capture: the least time the chip could take for the factor products'
work (2 x rows x side^2 per factor against the bf16 peak, their bytes against
the HBM peak) over ``capture_extra_ms`` (device time), in percent."""
LAYER = "capture"
MOVES = "samples_per_s"


def read(run):
    extra = run["read"]("capture_extra_ms")
    if extra is None or extra <= 0:
        return None
    least, _bound = run["least_seconds"](run["work"]["capture"])
    return 100.0 * least * 1e3 / extra
