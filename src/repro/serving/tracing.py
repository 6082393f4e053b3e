"""Host spans and phase counters for the serving loop.

`span(name, stats, key, **meta)` marks one region of host work.  It
enters a `jax.profiler.TraceAnnotation` of that name, which a running
profiler records on the host plane, on the same clock as the device's
ops (`meta`, such as `rid=`, becomes the event's stats; with no profiler
running an annotation costs about a microsecond).  Given a stats dict
and a key it also adds the region's host wall time to `stats[key]` on
exit, by return or exception, so the engine's phase counters stand
however `run()` ends.  The counters are only ever added to: a reader
takes differences of `engine.stats` across a run.

`loop_clock(stats)` does the same for the whole serving loop (`loop_s`)
and adds the garbage collector's seconds and collections inside it,
taken from a `gc.callbacks` hook that the first call installs once per
process.

jax is imported on the first span, not with this module, so that
host-only layers may import it and stay jax-free.  docs/serving.md
§Observability lists every span and counter and what reads it.
"""
from __future__ import annotations

import contextlib
import gc
import time

# every span the serving loop emits; the engine's run() holds them all
SPANS = ("admission_cycle", "idle_wait", "reconcile", "cold_prefill",
         "admit_cold", "admit_hit", "admit_lane", "decode_dispatch",
         "first_token_sync", "block_sync", "cold_admission", "loop_control")
# phase counters, host wall seconds, in engine.stats
PHASES = ("loop_s", "admit_s", "prefill_s", "decode_s", "sync_s",
          "reconcile_s", "idle_s", "gc_s")

_annotation = None  # jax.profiler.TraceAnnotation, resolved on first use
_gc = {"s": 0.0, "n": 0, "t": None}  # collector totals (gc_totals)


class _Span:
    __slots__ = ("_ann", "_stats", "_key", "_t")

    def __init__(self, ann, stats, key):
        self._ann, self._stats, self._key = ann, stats, key

    def __enter__(self):
        self._ann.__enter__()
        self._t = time.perf_counter()
        return self

    def __exit__(self, *exc):
        if self._stats is not None:
            self._stats[self._key] += time.perf_counter() - self._t
        return self._ann.__exit__(*exc)


def span(name: str, stats: dict | None = None, key: str | None = None,
         **meta) -> _Span:
    """A profiler annotation `name` (with `meta` as its stats) around a
    `with` block; with `stats`, the block's seconds are added to
    `stats[key]`."""
    global _annotation
    if _annotation is None:
        from jax.profiler import TraceAnnotation
        _annotation = TraceAnnotation
    return _Span(_annotation(name, **meta), stats, key)


def _on_gc(phase: str, info: dict) -> None:
    if phase == "start":
        _gc["t"] = time.perf_counter()
    elif _gc["t"] is not None:
        _gc["s"] += time.perf_counter() - _gc["t"]
        _gc["n"] += 1
        _gc["t"] = None


def gc_totals() -> tuple:
    """(seconds, collections) the garbage collector has taken since the
    hook was installed; the first call installs it."""
    if _on_gc not in gc.callbacks:
        gc.callbacks.append(_on_gc)
    return _gc["s"], _gc["n"]


@contextlib.contextmanager
def loop_clock(stats: dict):
    """Add the block's wall time to stats["loop_s"], and the collector's
    time and collections inside it to "gc_s" and "gc_collections"."""
    t, (g_s, g_n) = time.perf_counter(), gc_totals()
    try:
        yield
    finally:
        now_s, now_n = gc_totals()
        stats["loop_s"] += time.perf_counter() - t
        stats["gc_s"] += now_s - g_s
        stats["gc_collections"] += now_n - g_n
