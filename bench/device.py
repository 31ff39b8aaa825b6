"""The chip a run holds: its entry in the peak table, its memory peak,
and the compilations and compile-cache hits JAX reports."""
from __future__ import annotations

import json
from pathlib import Path

TABLE = Path(__file__).resolve().parent / "devices.json"

_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")


class NoChip(RuntimeError):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


def peaks(kind: str) -> dict:
    """Published peaks of a ``device_kind``; an unknown kind is an error."""
    table = json.loads(TABLE.read_text())
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in {TABLE.name}")
    return table[kind]


def info(chips: int, require_tpu: bool = True) -> dict:
    """Platform, kind and count of the devices the cell uses."""
    import jax

    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < chips):
        raise NoChip(f"the cell needs {chips} TPU chip(s); JAX found "
                     f"{len(devs)} {devs[0].platform} device(s)")
    return dict(platform=devs[0].platform, kind=devs[0].device_kind,
                count=chips)


def memory_peak_bytes(chips: int):
    """Peak bytes in use on the fullest chip, where the backend says."""
    import jax

    peak = [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in jax.devices()[:chips]]
    peak = [p for p in peak if p is not None]
    return max(peak) if peak else None


class CompileCounter:
    """Counts compilations and persistent-cache hits as JAX reports them
    (trace + lowering + backend compile durations, cache-hit events)."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.traces = 0
        self.compiles = 0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration_secs, **_):
        if event in _COMPILE_EVENTS:
            self.seconds += duration_secs
        if event == _COMPILE_EVENTS[0]:
            self.traces += 1
        if event == _COMPILE_EVENTS[2]:
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def snapshot(self) -> dict:
        """Counts so far."""
        return dict(traces=self.traces, compiles=self.compiles,
                    cache_hits=self.hits, compile_s=self.seconds)
