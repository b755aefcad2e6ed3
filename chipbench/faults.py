"""The control and the planted faults that the check must catch.

Never used by a benchmark run: `chipbench/control.py` drives them on the
chip and `chipbench/tests/` on the CPU.  Each `install(log)` breaks the
save path of one run and returns a function that undoes it.

* `control`: the state handed to the store is the live state rounded to
  the next precision below the one the configuration states (float32
  through bfloat16, bfloat16 through float8 e4m3), which a later change
  could be tempted to store.
* `stale`: every save hands the store its stream's first snapshot again,
  a step that returns its state unchanged.
* `half`: change detection reports every other dirty chunk only.
* `alter`: every serialized pod has one byte altered where it is made.
"""
from __future__ import annotations

import importlib
from typing import Any, Callable, Dict

from . import reference

LOWER = {"float32": "bfloat16", "bfloat16": "float8_e4m3fn",
         "float16": "float8_e4m3fn"}


def _lower_precision(snapshot: Any) -> Any:
    import jax
    import jax.numpy as jnp

    def cast(x):
        name = str(getattr(x, "dtype", ""))
        if name not in LOWER:
            return x
        return jnp.asarray(x).astype(LOWER[name]).astype(x.dtype)

    return jax.tree.map(cast, snapshot)


def control(log) -> Callable[[], None]:
    log.transform = lambda stream, snap: _lower_precision(snap)
    return lambda: None


def stale(log) -> Callable[[], None]:
    first: Dict[str, Any] = {}
    log.transform = lambda stream, snap: first.setdefault(stream, snap)
    return lambda: None


def _patch(module: str, owner: str, attr: str, make) -> Callable[[], None]:
    mod = importlib.import_module(module)
    target = getattr(mod, owner) if owner else mod
    orig = target.__dict__[attr]
    setattr(target, attr, make(orig))
    return lambda: setattr(target, attr, orig)


def half(log) -> Callable[[], None]:
    def make(detect):
        def detect_half(self, *args, **kwargs):
            report = detect(self, *args, **kwargs)
            report.dirty = set(sorted(report.dirty)[::2])
            return report
        return detect_half
    return _patch("repro.core.change_detector", "ChangeDetector", "detect",
                  make)


def alter(log) -> Callable[[], None]:
    def make(serialize_pod):
        def serialize_altered(*args, **kwargs):
            data = bytearray(serialize_pod(*args, **kwargs))
            data[-1] ^= 0x01
            return bytes(data)
        return serialize_altered
    return _patch("repro.core.checkpoint", None, "serialize_pod", make)


MODES = {"control": control, "stale": stale, "half": half, "alter": alter}
