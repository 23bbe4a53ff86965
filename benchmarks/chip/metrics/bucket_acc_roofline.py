"""Share of its roofline that the ``bucket_acc`` kernel reaches.

Time: device time of the ops under the ``bucket_acc`` scope, per chip.
Least time: per fold, the larger of its bytes over HBM bandwidth (read the
f32 accumulator, the int8 levels and the f32 row scales, write the f32
accumulator) and its FLOPs over the bf16 peak; the fold moves 9 bytes for
2 operations per element, so bandwidth bounds it.  Folds: the kernel's
launches in the trace (``bucket_acc`` custom calls), per chip."""
from harness import flops
from harness import trace as TR
from harness.peaks import peaks


def read(rec):
    red = rec.get("trace")
    if red is None or "bucket_layout" not in rec or not red.devices:
        return None
    b, r, c = rec["bucket_layout"]
    pk = peaks(rec["device_kind"])
    least = max(flops.bucket_acc_bytes(b, r, c) / pk["hbm_bytes_per_s"],
                flops.bucket_acc_flops(b, r, c) / pk["bf16_flops_per_s"])
    folds = sum(TR.count_ops(d, "bucket_acc", "bucket_acc")
                for d in red.devices) / len(red.devices)
    spent = TR.mean_scope_s(red, "bucket_acc")
    if folds == 0 or spent <= 0:
        return None
    return 100.0 * folds * least / spent
