"""whole step: model FLOPs per sample (forward and backward from
``work/``; K-FAC's own work not counted) x samples per second of the window,
over chips x the bf16 peak, in percent."""
LAYER = "whole step"
MOVES = "samples_per_s"


def read(run):
    rate = len(run["records"]) * run["samples_per_step"] / run["window_s"]
    peak = run["chips"] * run["peak"]["bf16_flops_per_s"]
    return 100.0 * run["work"]["model_flops_per_sample"] * rate / peak
