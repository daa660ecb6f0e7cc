"""apply: the least time the chip could take for the apply's work (``work/``:
the larger of its operations over the bf16 peak and its bytes over the HBM
peak) over ``apply_scope_ms``, in percent."""
LAYER = "apply"
MOVES = "samples_per_s"


def read(run):
    ms = run["read"]("apply_scope_ms")
    if not ms:
        return None
    least, _bound = run["least_seconds"](run["work"]["apply"])
    return 100.0 * least * 1e3 / ms
