"""apply: the least time the chip could take for the apply's work (from
``work/``: the larger of its operations over the bf16 peak and its bytes over
the HBM peak) over ``apply_extra_ms`` (device time), in percent. Nothing where the extra
time is not above zero."""
LAYER = "apply"
MOVES = "samples_per_s"


def read(run):
    extra = run["read"]("apply_extra_ms")
    if extra is None or extra <= 0:
        return None
    least, _bound = run["least_seconds"](run["work"]["apply"])
    return 100.0 * least * 1e3 / extra
