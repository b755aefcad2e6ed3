"""`save_hbm_roofline`: the save path's share of the HBM roofline, in %.

The least device time a save can take is reading each byte of its active
device leaves once at peak HBM bandwidth.  The time it took is the device
busy time of every operation in the traced window outside the traffic's
own jitted step, which is the pack, the fingerprint kernel, its compare,
the digest table and the payload fetches of the saves.  Whatever implements a save, the
yardstick reads the same work; a share above 100% means the bytes or the
time are counted wrong, and is an error.
"""
from __future__ import annotations


def read(view):
    t = view.trace
    if t is None or t.save_busy_s <= 0 or not view.saves:
        return None
    least_s = sum(r.active_device_bytes for r in view.saves) / \
        view.peaks["hbm_bytes_per_s"]
    share = least_s / t.save_busy_s * 100.0
    if share > 100.0:
        raise ValueError(f"save_hbm_roofline {share:.3f}% is above 100%: "
                         "bytes over-counted or device time missed")
    return share
