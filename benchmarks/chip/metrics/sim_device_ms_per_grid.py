"""Device busy time per grid call in the traced window, in milliseconds."""
from harness import trace as TR


def read(rec):
    red = rec.get("trace")
    if red is None or not rec.get("calls") or not red.devices:
        return None
    return 1e3 * TR.mean_busy_s(red) / rec["calls"]
