"""Share of the bf16 peak that the held experts' matmuls reach.

Operations: the step's token-expert assignments to held experts
(``moe_held_assignments``, the program's counter, averaged over the traced
window's steps) times one assignment's three [d, f] products
(``harness.flops_lm``), forward plus backward (x3), no remat, as
``train_mfu`` counts.  Time: device time under the ``moe/experts`` scope
per step (the grouped matmuls, whatever implements them, with the rows'
gather and the weighted scatter back; XLA's ``ragged-dot-*`` Mosaic calls
carry no scope and are counted here, ``harness.scopes``), per chip.  The products are
compute-bound at these widths, so the bf16 peak is the roof.  None without
a trace, the counter or such ops."""
from harness import scopes
from harness.peaks import peaks


def read(rec):
    if "moe_held_assignments" not in rec:
        return None
    spent = scopes.scope_s_per_step(rec, "moe/experts",
                                    also=scopes.RAGGED_DOT)
    if spent is None:
        return None
    work = 3.0 * rec["moe_held_assignments"] * rec["expert_flops_per_assignment"]
    return 100.0 * work / (spent * peaks(rec["device_kind"])["bf16_flops_per_s"])
