"""Device milliseconds per step under the train step's ``wire`` scope
(``core/dist.py``): bucketize, uplink encode, the ring, the memory update,
the downlink and unbucketize.

Ops are matched to the scope through the compiled HLO's ``op_name``
(``harness.trace``): a fusion counts under the scope of its root
instruction, a loop under the scope of its ``while`` (the loop's interval
holds its body's ops).  Per chip, over the traced window's steps; none
without a trace or where no op carries the scope."""
from harness import trace as TR


def read(rec):
    red = rec.get("trace")
    if red is None or not rec.get("steps") or not red.devices:
        return None
    spent = TR.mean_scope_s(red, "wire")
    return 1e3 * spent / rec["steps"] if spent > 0 else None
