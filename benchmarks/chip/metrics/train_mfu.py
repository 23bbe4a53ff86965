"""Model FLOP utilization of the train step: forward and backward matmul
FLOPs per step from the shapes (``harness.flops``, no recomputation), times
the steps of the traced window, over the window and the chips' bf16 peak."""
from harness.peaks import peaks


def read(rec):
    if "train_flops_per_step" not in rec or not rec.get("steps"):
        return None
    peak = peaks(rec["device_kind"])["bf16_flops_per_s"] * rec["chips"]
    return 100.0 * rec["train_flops_per_step"] * rec["steps"] / (
        rec["window_s"] * peak)
