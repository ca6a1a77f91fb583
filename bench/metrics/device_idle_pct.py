"""device_idle_pct — the device.

Share of the traced window in which no operation ran on the device:
100 * (1 - union of the device-op intervals / window), averaged over the
chips used.
"""
from bench import trace


def read(ctx):
    t = ctx["trace"]
    if not t.ops or t.window_s <= 0:
        return None
    return 100.0 * (1.0 - trace.busy_s(t) / t.window_s)
