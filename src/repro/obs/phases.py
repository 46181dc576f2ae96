"""Names for the phases of the simulator's step and of set-up.

One vocabulary serves the device's clock and the host's (DESIGN.md §8,
tier 2):

* :func:`scope` opens a ``jax.named_scope``: every op traced inside it
  carries the name in its ``op_name`` metadata, which the compiled HLO
  and a profiler capture's device ops both show.  While JAX traces the
  body, the scope also adds the host seconds spent inside it, less those
  of the scopes nested in it, to each open :class:`Record`.  That Python
  runs only at trace time: the compiled program gains metadata and
  nothing else.
* :func:`phase` is the scope ``step:<name>`` of one of :data:`PHASES`:
  the sections of ``array_sim.sim.make_step`` and the glue of
  ``make_runner``'s loop nest.  ``kernels.ops`` opens its ``kernel:*``
  scopes through :func:`scope`, so a kernel nests inside its phase, as in
  ``step:io/kernel:fifo_grant``.  :func:`phase_of` reads the phase back
  from an ``op_name``.
* :func:`span` times a stretch of host work (``setup:trace`` and the
  other set-up spans): it adds ``(name, start, end)`` on
  ``time.perf_counter`` to each open :class:`Record` and opens a
  ``jax.profiler.TraceAnnotation`` of the same name, so a profiler
  capture puts the span on the timeline of the device ops.
"""

from __future__ import annotations

import contextlib
import threading
import time
from typing import Dict, Iterator, List, Optional, Tuple

import jax

PREFIX = "step:"
#: the step's phases, in the order the step runs them; ``loop`` is the
#: runner's loop nest around the step (conditions, carry threading)
PHASES = ("cpu", "demand", "io", "evict", "obs", "horizon", "loop")

_open: List["Record"] = []
_local = threading.local()


class Record:
    """What scopes and spans report while the record is open
    (``with record:``): host seconds inside each scope while JAX traced
    it, and the spans in the order they closed."""

    def __init__(self) -> None:
        self.trace_seconds: Dict[str, float] = {}
        self.spans: List[Tuple[str, float, float]] = []

    def __enter__(self) -> "Record":
        _open.append(self)
        return self

    def __exit__(self, *exc) -> None:
        _open.remove(self)


@contextlib.contextmanager
def scope(name: str) -> Iterator[None]:
    """``jax.named_scope(name)``, with its host seconds tallied."""
    nested = _local.__dict__.setdefault("nested", [])
    nested.append(0.0)
    t0 = time.perf_counter()
    try:
        with jax.named_scope(name):
            yield
    finally:
        dt = time.perf_counter() - t0
        own = dt - nested.pop()
        if nested:
            nested[-1] += dt
        for rec in _open:
            rec.trace_seconds[name] = rec.trace_seconds.get(name, 0.0) + own


def phase(name: str):
    """The scope ``step:<name>`` of one of :data:`PHASES`."""
    if name not in PHASES:
        raise ValueError(f"unknown step phase {name!r}; phases: {PHASES}")
    return scope(PREFIX + name)


def phase_of(op_name: str) -> Optional[str]:
    """The innermost ``step:`` segment of an ``op_name``, or None."""
    for seg in reversed(op_name.split("/")):
        if seg.startswith(PREFIX):
            return seg
    return None


@contextlib.contextmanager
def span(name: str, start: Optional[float] = None) -> Iterator[None]:
    """Time the block as the span ``name``.  ``start``, a
    ``time.perf_counter`` reading, dates the span from before the block
    (the process's start, say); the profiler annotation covers the block
    alone."""
    t0 = time.perf_counter() if start is None else start
    try:
        with jax.profiler.TraceAnnotation(name):
            yield
    finally:
        end = time.perf_counter()
        for rec in _open:
            rec.spans.append((name, t0, end))
