"""Structured observability for the peel-to-serve stack.

Three parts (see docs/OBSERVABILITY.md):

* ``obs.trace``  — host-side span recorder with Chrome-trace export
  (Perfetto-loadable) and ``jax.profiler.TraceAnnotation`` bridging;
* ``obs.timeline`` — per-round peel timelines: CD rounds recorded live,
  FD rounds drained from device counter rings threaded through the FD
  ``while_loop`` carries;
* ``obs.metrics`` — counters / gauges / fixed-bucket latency histograms
  (p50/p99) for the serving layer, with a JSON snapshot exporter.

The whole layer is gated by :func:`enable` / :func:`disable`.  **Off
(the default) is zero-overhead**: no ring code is traced, so every
structural jaxpr invariant (single-``while`` FD, one-``pallas_call``
fused body, one-psum CD, loop-free dispatch) sees the byte-identical
program — asserted against ``tests/goldens/obs_jaxprs.json``.
``enable(timeline=False)`` turns on spans and counters alone: no
collector, no ring code, the same device programs as off.

Set ``REPRO_OBS=1`` to enable at import time (CI trace jobs), and
``REPRO_OBS_RING_CAP`` to size the per-round FD rings (default 1024).
"""
from __future__ import annotations

import os as _os

from .metrics import (Counter, Gauge, Histogram,  # noqa: F401
                      MetricsRegistry, percentiles)
from .timeline import (PeelTimeline, TimelineCollector,  # noqa: F401
                       RING_CAP_DEFAULT, fd_ring_cap, maybe_collect)
from .timeline import active as active_collector  # noqa: F401
from .trace import (ANNOTATION_PREFIX, Tracer, counter,  # noqa: F401
                    disable, enable, enabled, get_tracer, instant, span,
                    timeline_enabled)

__all__ = [
    "Tracer", "enable", "disable", "enabled", "timeline_enabled",
    "get_tracer", "span", "instant", "counter", "ANNOTATION_PREFIX",
    "PeelTimeline", "TimelineCollector", "RING_CAP_DEFAULT",
    "fd_ring_cap", "maybe_collect", "active_collector",
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "percentiles",
]

if _os.environ.get("REPRO_OBS", "") in ("1", "true", "yes"):
    enable()
