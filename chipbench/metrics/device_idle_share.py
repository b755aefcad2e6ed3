"""`device_idle_share`: the share of the traced window, in %, in which no
operation ran on the device (busy time is the union of the operations'
intervals, averaged over the chips)."""
from __future__ import annotations


def read(view):
    t = view.trace
    if t is None or t.window_s <= 0:
        return None
    return (1.0 - t.busy_s / t.window_s) * 100.0
