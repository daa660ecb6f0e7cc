"""attention: the least time the chip could take for one step's causal
attention (``work/``: the two products of the forward pass and the five of
the backward pass over the causal pairs against the bf16 peak, q, k, v, o and
their gradients against the HBM peak) over ``attention_scope_ms``, in
percent."""
LAYER = "attention"
MOVES = "samples_per_s"


def read(run):
    ms = run["read"]("attention_scope_ms")
    if not ms or "attention" not in run["work"]:
        return None
    least, _bound = run["least_seconds"](run["work"]["attention"])
    return 100.0 * least * 1e3 / ms
