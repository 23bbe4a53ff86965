"""Share of the traced window in which no op ran on the device, for the
sweep engine's grid calls: 1 - union of op intervals / window."""
from harness import trace as TR


def read(rec):
    red = rec.get("trace")
    if red is None or "cells" not in rec or not red.devices:
        return None
    return 100.0 * (1.0 - TR.mean_busy_s(red) / red.window_s)
