"""Operations and bytes of the K-FAC work per layer, from shapes alone, and
the least time a chip could take for them. Independent of which
implementation runs. A layer is ``{"name", "a_side", "g_side", "rows",
"in_elems", "out_elems"}``: the sides of its two factors, the rows its
statistics sum over in one step (batch x positions), and the elements of its
input and output activations in one step."""

from __future__ import annotations

F32 = 4


def capture_work(layers):
    """One capture step: A = X^T X and G = Y^T Y for every layer
    (2 x rows x side^2 each), reading each activation once and reading and
    writing each running average once."""
    flops = sum(2 * l["rows"] * (l["a_side"] ** 2 + l["g_side"] ** 2) for l in layers)
    byts = sum(
        F32 * (l["in_elems"] + l["out_elems"] + 2 * (l["a_side"] ** 2 + l["g_side"] ** 2))
        for l in layers
    )
    return {"flops": flops, "bytes": byts}


def apply_work(layers):
    """One step's preconditioning on the inverse path: v = iG g iA for every
    layer (2 g^2 a + 2 g a^2), reading both inverses and the gradient once
    and writing the result once."""
    flops = sum(2 * l["g_side"] ** 2 * l["a_side"] + 2 * l["g_side"] * l["a_side"] ** 2 for l in layers)
    byts = sum(
        F32 * (l["a_side"] ** 2 + l["g_side"] ** 2 + 2 * l["a_side"] * l["g_side"]) for l in layers
    )
    return {"flops": flops, "bytes": byts}


def kfac_work(layers):
    """Capture, apply and the elements of all factors, for a layer list."""
    return {
        "capture": capture_work(layers),
        "apply": apply_work(layers),
        "factor_elements": sum(l["a_side"] ** 2 + l["g_side"] ** 2 for l in layers),
    }


def least_seconds(work, peak, chips=1):
    """``(seconds, bound)``: the larger of operations over the bf16 peak and
    bytes over the HBM peak, and which of the two it is."""
    t_flops = work["flops"] / (chips * peak["bf16_flops_per_s"])
    t_bytes = work["bytes"] / (chips * peak["hbm_bytes_per_s"])
    return (t_flops, "compute") if t_flops >= t_bytes else (t_bytes, "memory")
