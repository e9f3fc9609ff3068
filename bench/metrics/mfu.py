"""Model FLOPs utilization of the co-simulated training: the model's
FLOPs per sample (forward and backward, from shapes, in the
configuration's file) times this run's samples per second, over one
chip's bf16 peak."""


def read(mi):
    w = mi.work
    if "flops_per_sample" not in w or not mi.peaks:
        return None
    rate = w["samples"] / w["wall_s"]
    return 100.0 * w["flops_per_sample"] * rate / (
        mi.chips * mi.peaks["flops_bf16"])
