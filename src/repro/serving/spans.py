"""Host spans of the serving engine.

``with span(acc, "engine.readback", cycle=7):`` marks a piece of host work
twice:

* as a ``jax.profiler.TraceAnnotation``, so a profiler trace shows it on
  the host's timeline, on the device trace's clock, with its keyword
  arguments as stats (the annotation formats nothing while no profiler
  is running);
* by adding its host seconds to ``acc[name]``, so the time is readable
  without a trace (two ``perf_counter`` calls per span).

A span's seconds include those of the spans nested inside it.
"""
from __future__ import annotations

import time
from typing import Dict

from jax.profiler import TraceAnnotation


class span:
    __slots__ = ("_acc", "_name", "_ann", "_t0")

    def __init__(self, acc: Dict[str, float], name: str, **args):
        self._acc, self._name = acc, name
        self._ann = TraceAnnotation(name, **args)

    def __enter__(self) -> None:
        self._ann.__enter__()
        self._t0 = time.perf_counter()

    def __exit__(self, *exc) -> None:
        dt = time.perf_counter() - self._t0
        self._ann.__exit__(*exc)
        self._acc[self._name] = self._acc.get(self._name, 0.0) + dt
