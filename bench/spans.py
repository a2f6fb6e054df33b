"""Host spans the benchmark records around its calls into the program.

Each span is kept in memory (name, start and end on the monotonic clock)
and, while a profiler trace runs, written into the trace as a
``TraceAnnotation`` so that device idle gaps can be set against them.
"""
from __future__ import annotations

import contextlib
import time
from typing import List, Tuple


class Spans:
    def __init__(self):
        self.done: List[Tuple[str, int, int]] = []

    @contextlib.contextmanager
    def __call__(self, name: str):
        import jax
        t0 = time.monotonic_ns()
        try:
            with jax.profiler.TraceAnnotation(name):
                yield
        finally:
            self.done.append((name, t0, time.monotonic_ns()))

    def clear(self) -> None:
        self.done.clear()
