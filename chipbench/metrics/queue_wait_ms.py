"""`queue_wait_ms`: a save body's wait in the queue, from its enqueue
on the caller's thread to its start on the podding thread, in ms: mean
`t_queue` of the saves committed in the window (the program's own
stamps)."""
from __future__ import annotations

from chipbench.phase_stats import mean_ms


def read(view):
    return mean_ms(view, "t_queue")
