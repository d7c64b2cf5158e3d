"""Named spans and per-stage records of the stage pipeline.

A *span* is a named interval of one thread's work.  :class:`span` opens a
``jax.profiler.TraceAnnotation`` under its name — so while a profiler
session runs, the span lands in the trace on the same clock as the
device's ops — and adds its ``perf_counter`` seconds to a
:class:`StageRecord`, the in-memory view that needs no profiler.  The
record is always passed explicitly: the pipeline's worker threads run
spans on behalf of the stage the dispatch thread is in.

Every stage the pipeline runs leaves one :class:`StageRecord` in a
process-wide ring of the last 4096 (like ``logging``'s root logger, one
per process); :func:`recent` reads it.  A record's ``run`` numbers one
``Simulator.run`` / ``run_batch`` call (:func:`next_run`; the sub-batch
chunks of a ``run_batch`` share it).  Job start, the |0..0> init, has a
record of its own with ``stage = -1``.

The spans, by thread (``a > b``: ``b`` runs inside ``a``):

=============  ==============================================================
thread         spans
=============  ==============================================================
dispatch       ``stage`` > ``pipeline.load_wait`` (a fetched wave from the
               ready queue), ``pipeline.dispatch`` (h2d staging, the stage
               program, the encode dispatch), ``pipeline.device_wait``
               (until the device has computed a wave's result),
               ``pipeline.d2h_wait`` (the d2h copy of that ready result),
               ``pipeline.store_wait`` (the stage barrier on the store
               pool); ``run.init``
load worker    ``pipeline.load`` > ``store.get``, ``codec.inflate``
store worker   ``pipeline.store`` > ``codec.deflate``, ``store.put`` (one
               block a call; the workers run them at once)
=============  ==============================================================

Without worker pools (``pipeline_depth`` 1 or a single-core host) the
dispatch thread runs ``pipeline.load`` and ``pipeline.store`` itself and
has no ``*_wait`` on the pools.  Span names hold no space and do not
start with ``$`` or ``bench.``, so a trace's idle gaps can be named by
them.

The spans are always on: with no profiler session running a span costs
a few microseconds of host time.
"""
from __future__ import annotations

import itertools
import threading
import time
from collections import deque
from contextlib import contextmanager
from dataclasses import dataclass, field

import jax

__all__ = ["StageRecord", "span", "stage_record", "next_run", "recent",
           "per_stage_s"]

_RING_SIZE = 4096

_ring: deque = deque(maxlen=_RING_SIZE)
_ring_lock = threading.Lock()
_runs = itertools.count()


def next_run() -> int:
    """A new process-wide run number."""
    return next(_runs)


@dataclass
class StageRecord:
    """What one stage run did and where its threads spent their time.

    ``span_s`` maps a span name to its summed seconds over every thread
    of the stage.  The counters are exact: blocks fetched from and
    written to the store, the written blocks that took the codec's RAW
    escape (incompressible), bytes across the host-device boundary each
    way, and the store's total bytes when the stage ended.
    ``store_peak_inflight`` is the most store calls that ran at once in
    the stage: 1 where the dispatch thread stores (depth 1, no pools),
    up to the store pool's width where each block is its own task.
    """

    run: int
    stage: int
    lanes: int = 1
    groups: int = 0
    waves: int = 0
    wall_s: float = 0.0
    span_s: dict = field(default_factory=dict)
    blocks_loaded: int = 0
    blocks_stored: int = 0
    raw_blocks_stored: int = 0
    h2d_bytes: int = 0
    d2h_bytes: int = 0
    stored_bytes: int = 0
    store_peak_inflight: int = 0
    _lock: threading.Lock = field(default_factory=threading.Lock,
                                  repr=False, compare=False)

    def add_span(self, name: str, seconds: float) -> None:
        with self._lock:
            self.span_s[name] = self.span_s.get(name, 0.0) + seconds


class span:
    """Time the block as span ``name`` of ``rec`` (``None``: trace only).

    A class, not a generator: it runs in every block's store and codec
    call, where a generator's extra frames cost more than the span.
    """

    __slots__ = ("_name", "_rec", "_ann", "_t0")

    def __init__(self, name: str, rec: StageRecord | None):
        self._name = name
        self._rec = rec
        self._ann = jax.profiler.TraceAnnotation(name)

    def __enter__(self) -> None:
        self._ann.__enter__()
        self._t0 = time.perf_counter()

    def __exit__(self, *exc) -> None:
        dt = time.perf_counter() - self._t0
        self._ann.__exit__(*exc)
        if self._rec is not None:
            self._rec.add_span(self._name, dt)


@contextmanager
def stage_record(name: str, run: int, stage: int, lanes: int = 1):
    """A new :class:`StageRecord` whose ``wall_s`` is the block, timed as
    span ``name``; it joins the ring once the block finishes."""
    rec = StageRecord(run, stage, lanes)
    with span(name, rec):
        yield rec
    rec.wall_s = rec.span_s[name]
    with _ring_lock:
        _ring.append(rec)


def recent(run: int | None = None) -> list[StageRecord]:
    """The ring's finished records, oldest first (only run ``run``'s if
    given)."""
    with _ring_lock:
        recs = list(_ring)
    return recs if run is None else [r for r in recs if r.run == run]


def per_stage_s(records) -> dict[str, float]:
    """Each span's mean seconds per stage over the stage records among
    ``records`` (job start excluded)."""
    stages = [r for r in records if r.stage >= 0]
    total: dict[str, float] = {}
    for r in stages:
        for k, v in r.span_s.items():
            total[k] = total.get(k, 0.0) + v
    return {k: v / len(stages) for k, v in total.items()}
