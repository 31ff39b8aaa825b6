"""Host-side span tracing with Chrome-trace export.

One module-level switch (``enable()`` / ``disable()``) gates the whole
observability layer: with it off (the default) every ``span()`` /
``instant()`` call returns a shared null object and the peel core picks
a zero ring capacity, so the traced jaxprs are byte-identical to the
uninstrumented tree (``tests/goldens/obs_jaxprs.json``).
``enable(timeline=False)`` records spans, instants and counters but
installs no timeline collector, so the FD ring capacity stays 0 and
every device program is the one the layer-off path runs: host timing
without changing what it times.

With it on, a :class:`Tracer` records nested spans (Chrome-trace
"complete" events, ``ph="X"``), instants (``ph="i"``) and counter
samples (``ph="C"``) with categories and JSON-able args.  ``save()``
writes the standard ``{"traceEvents": [...]}`` envelope, loadable in
Perfetto (https://ui.perfetto.dev) or ``chrome://tracing``.  Host spans
also enter ``jax.profiler.TraceAnnotation`` as ``repro.<name>``, so
device work lines up under them, on one clock, when a jax profile is
being captured concurrently.

Span taxonomy (see docs/OBSERVABILITY.md):

====================  ==========  ===========================================
cat                   ph          meaning
====================  ==========  ===========================================
``graph``             X           ``graph.from_edges``: one canonical graph
                                  build (bounds check, dedup, sort); args
                                  ``m_in``, ``m_out``
``peel``              X           one ``decompose()`` / distributed run
``cd``                X           Phase 1 (cover decomposition) total
``cd.select``         X           one partition's range selection; count ==
                                  ``p_effective``
``cd.round``          X           one masked peel round; count == ``rho_cd``
``cd.step``           X           the round's ``cd_step`` (upload, update,
                                  readback); count == ``rho_cd``
``fd``                X           Phase 2 (fine decomposition) total
``fd.launch``         X           one FD dispatch (``fd.partition``, or the
                                  one ``fd.vmapped`` launch covering all)
``fd.pack``           X           host packing + uploads before a csr
                                  partition's launch
``fd.round``          i           one partition-round; count == rho_fd_total
                                  (timeline mode only)
``hierarchy``         X           hierarchy build steps (labels, wedges,
                                  assemble, node_stats)
``serve``             X           ``query_batch`` and its admit / pack /
                                  dispatch parts; pool admission
====================  ==========  ===========================================
"""
from __future__ import annotations

import json
import threading
import time
from contextlib import contextmanager
from typing import Any, Dict, Iterator, List, Optional

try:  # pragma: no cover - import guard only
    from jax.profiler import TraceAnnotation as _TraceAnnotation
except Exception:  # pragma: no cover
    _TraceAnnotation = None

__all__ = [
    "Tracer", "enable", "disable", "enabled", "timeline_enabled",
    "get_tracer", "span", "instant", "counter", "ANNOTATION_PREFIX",
]

# a span's name in a concurrent jax profile: ``repro.<name>``
ANNOTATION_PREFIX = "repro."


def _jsonable(v: Any) -> Any:
    """Coerce numpy scalars / arrays into plain JSON values."""
    if isinstance(v, (str, bool)) or v is None:
        return v
    if hasattr(v, "tolist"):          # numpy scalar or array
        return v.tolist()
    if isinstance(v, (int, float)):
        return v
    return str(v)


class _NullSpan:
    """Context manager returned when tracing is disabled."""
    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


class Tracer:
    """Records Chrome-trace events; timestamps are microseconds since
    the tracer was created (Chrome-trace native unit)."""

    def __init__(self) -> None:
        self._t0 = time.perf_counter()
        self._lock = threading.Lock()
        self.events: List[Dict[str, Any]] = []

    # -- recording ---------------------------------------------------
    def now(self) -> float:
        """Microseconds since tracer start."""
        return (time.perf_counter() - self._t0) * 1e6

    @contextmanager
    def span(self, name: str, cat: str = "",
             **args: Any) -> Iterator[Dict[str, Any]]:
        """Record a complete event around the block.  Yields a dict the
        block may fill with late args (values only known mid-span, e.g.
        a round's update delta) — merged into the event at exit."""
        t0 = self.now()
        late: Dict[str, Any] = {}
        ann = (_TraceAnnotation(ANNOTATION_PREFIX + name)
               if _TraceAnnotation is not None else None)
        if ann is not None:
            ann.__enter__()
        try:
            yield late
        finally:
            if ann is not None:
                ann.__exit__(None, None, None)
            args.update(late)
            ev: Dict[str, Any] = dict(
                name=name, cat=cat or name, ph="X", ts=t0,
                dur=self.now() - t0, pid=0, tid=0)
            if args:
                ev["args"] = {k: _jsonable(v) for k, v in args.items()}
            with self._lock:
                self.events.append(ev)

    def instant(self, name: str, cat: str = "",
                ts: Optional[float] = None, **args: Any) -> None:
        """Record a zero-duration event (Chrome-trace ``ph="i"``)."""
        ev: Dict[str, Any] = dict(
            name=name, cat=cat or name, ph="i", s="t",
            ts=self.now() if ts is None else ts, pid=0, tid=0)
        if args:
            ev["args"] = {k: _jsonable(v) for k, v in args.items()}
        with self._lock:
            self.events.append(ev)

    def counter(self, name: str, values: Dict[str, Any],
                ts: Optional[float] = None) -> None:
        """A counter-track sample (renders as a curve in Perfetto)."""
        ev = dict(name=name, cat=name, ph="C",
                  ts=self.now() if ts is None else ts, pid=0, tid=0,
                  args={k: _jsonable(v) for k, v in values.items()})
        with self._lock:
            self.events.append(ev)

    # -- queries (used by the trace/stats exact-match tests) ---------
    def spans(self, cat: Optional[str] = None,
              ph: Optional[str] = None) -> List[Dict[str, Any]]:
        """Events filtered by category and/or phase."""
        return [e for e in self.events
                if (cat is None or e.get("cat") == cat)
                and (ph is None or e.get("ph") == ph)]

    def count(self, cat: Optional[str] = None,
              ph: Optional[str] = None) -> int:
        """Number of events matching the category/phase filter."""
        return len(self.spans(cat, ph))

    def sum_arg(self, key: str, cat: Optional[str] = None) -> int:
        """Sum an integer arg over every matching event."""
        return sum(int(e.get("args", {}).get(key, 0))
                   for e in self.spans(cat))

    # -- export ------------------------------------------------------
    def to_chrome(self) -> Dict[str, Any]:
        """The standard Chrome-trace envelope (Perfetto-loadable)."""
        return {"traceEvents": list(self.events),
                "displayTimeUnit": "ms"}

    def save(self, path: str) -> None:
        """Write :meth:`to_chrome` as JSON to ``path``."""
        with open(path, "w") as f:
            json.dump(self.to_chrome(), f)


# ----------------------------------------------------------------------
# Module-level gate.  ALL instrumentation in the peel core / hierarchy /
# serving layer routes through these helpers so the off path costs one
# ``is None`` check and changes no traced program.
# ----------------------------------------------------------------------
_tracer: Optional[Tracer] = None
_timeline = True


def enable(timeline: bool = True) -> Tracer:
    """Turn the observability layer on; returns the active tracer
    (fresh on the first call, reused afterwards).

    ``timeline=False`` is the spans-only mode: spans, instants and
    counters record, but no timeline collector is installed, so no FD
    counter-ring program is traced and no ``fd.round`` instants are
    synthesised.  Every device program is then the layer-off one."""
    global _tracer, _timeline
    if _tracer is None:
        _tracer = Tracer()
    _timeline = bool(timeline)
    return _tracer


def disable() -> None:
    """Turn the observability layer off and drop the tracer."""
    global _tracer
    _tracer = None


def enabled() -> bool:
    """Whether the observability layer is on."""
    return _tracer is not None


def timeline_enabled() -> bool:
    """Whether the layer is on with its per-round timeline collector."""
    return _tracer is not None and _timeline


def get_tracer() -> Optional[Tracer]:
    """The active tracer, or ``None`` when the layer is off."""
    return _tracer


def span(name: str, cat: str = "", **args: Any):
    """Module-level :meth:`Tracer.span`; inert null span when off."""
    t = _tracer
    return t.span(name, cat, **args) if t is not None else _NULL_SPAN


def instant(name: str, cat: str = "", **args: Any) -> None:
    """Module-level :meth:`Tracer.instant`; no-op when off."""
    t = _tracer
    if t is not None:
        t.instant(name, cat, **args)


def counter(name: str, values: Dict[str, Any]) -> None:
    """Module-level :meth:`Tracer.counter`; no-op when off."""
    t = _tracer
    if t is not None:
        t.counter(name, values)
