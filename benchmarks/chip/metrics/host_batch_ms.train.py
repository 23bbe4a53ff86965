"""Host time per step spent making the step's input (the harness span
``bench/batch`` around the program's ``TokenStream`` / ``ShardedBatches``
and the frame draw), in milliseconds."""


def read(rec):
    if "host_batch_s" not in rec or not rec.get("steps"):
        return None
    return 1e3 * rec["host_batch_s"] / rec["steps"]
