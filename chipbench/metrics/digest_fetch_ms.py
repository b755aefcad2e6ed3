"""`digest_fetch_ms`: the digest phase's one device-to-host fetch of
digests, dirty masks and speculated payload, in ms: mean
`t_digest_fetch` of the saves committed in the window (the program's
own phase timer)."""
from __future__ import annotations

from chipbench.phase_stats import mean_ms


def read(view):
    return mean_ms(view, "t_digest_fetch")
