"""train.mfu: the training steps' model FLOPs (``counts.mf_step_flops``,
from shapes) over the window's host-clock seconds and the chip's bf16 peak,
in percent."""


def read(rec):
    if not rec.get("steps"):
        return None
    flops = rec["steps"] * rec["flops_per_step"]
    return 100.0 * flops / (rec["window_host_s"] * rec["peak"]["bf16_flops_per_s"])
