"""What the per-layer metrics read from the program's own phase timers
and counters, each save's `save_stats` entry."""
from __future__ import annotations

from typing import Optional


def mean_ms(view, key: str) -> Optional[float]:
    """Mean of the timer `key` over the window's committed saves, in ms;
    None where no save has it (a program without that timer)."""
    xs = [r.stats[key] for r in view.saves if key in r.stats]
    return sum(xs) / len(xs) * 1e3 if xs else None
