"""Stage pipeline: load/decode → compute → encode/store (paper §4.1/§4.2).

One stage of the partitioned simulation processes every SV group through
three phases:

    1. load/decode   — fetch the group's 2^m blocks from the two-level
                       store and produce the flat 2^(b+m) device array
    2. compute       — apply the stage's fused unitaries on-device
    3. encode/store  — compress the updated blocks back into the store

:class:`StagePipeline` owns the phase orchestration; a
:class:`CodecBackend` decides *where the codec runs*:

``host``   (:class:`HostCodecBackend`)   — the correctness baseline: blocks
    are fully decompressed on the host and the **raw** 2^(b+m) complex64
    group array crosses the host↔device boundary (8 bytes/amplitude each
    way).

``device`` (:class:`DeviceCodecBackend`) — the paper's design: only the
    **compressed wire representation** (packed uint16 codes + ballot sign
    words + ``l_max`` scalars, ~4.25 bytes/amplitude) crosses the boundary;
    the Pallas kernels quantize/dequantize next to the compute, and the
    host keeps only the lossless zlib/prescan stage and the store.

The pipeline (§4.2's transfer-concealed workflow) is **wave-coalesced and
double-buffered**:

* ``pipeline_depth`` is the *wave width*: ``depth`` consecutive groups are
  coalesced into one wave that flows through the backend's ``*_batch``
  hooks — ONE stacked boundary crossing and ONE jitted dispatch per phase
  cover the whole wave, amortizing the per-call dispatch overhead that
  dominates the small-block configs (the same mechanism that makes
  ``run_batch`` beat K sequential runs).
* the blocking device→host wait sits in a bounded **in-flight window**:
  wave *w*'s result is only awaited after wave *w+1*'s compute and encode
  have been dispatched, so the await overlaps the next wave's device work
  under JAX's async dispatch.
* the host codec halves run on worker pools behind a completion
  **ready-queue**: fetches are submitted ahead (bounded lookahead) and the
  compute loop consumes them in *completion* order, so one slow decode
  never serializes the loop.  Each awaited wave fans out into one store
  task per block (:meth:`CodecBackend.split_store_batch`), which the
  store pool deflates at once — one worker per core left after the
  dispatch thread and the fetch workers — and the stage barriers on
  them all.  zlib releases the GIL, so the blocks' deflates run in
  parallel and store the same bytes a single worker would.

``depth=1`` degenerates to a strictly sequential
fetch→stage→compute→await→store loop on the caller's thread (no pools, no
lookahead) — the reference schedule the overlap tests compare against.
On a **single-core host** depth>1 keeps the wave coalescing (the
dispatch-amortization win needs no threads) but also runs sequentially:
worker pools whose context switches and GIL handoffs cost more than the
overlap they hide are never created unless ``fetch_workers`` explicitly
asks for them.

Both backends read and write the same stored :class:`BlockSegments`
format, so they are interchangeable mid-simulation and verifiable against
each other (tests/test_pipeline.py).
"""
from __future__ import annotations

import os
import queue
import threading
import warnings
from collections import deque
from concurrent.futures import ThreadPoolExecutor, wait

import jax
import jax.numpy as jnp
import numpy as np

from ..compression.codec import decode_block_host, encode_block_host
from ..compression.device_codec import (decode_blocks_planes,
                                        encode_group_planes,
                                        fetch_group_wire, segments_to_wire,
                                        wire_to_segments)
from ..compression.pwrel import PwRelParams
from ..compression.store import BlockStore
from ..errors import BlockCorruptionError, StoreIOError
from ..spans import StageRecord, next_run, span, stage_record
from .faults import fault_point

__all__ = ["CodecBackend", "HostCodecBackend", "DeviceCodecBackend",
           "StagePipeline", "make_backend",
           "complex_to_planes", "planes_to_complex"]


def complex_to_planes(amps: jax.Array) -> jax.Array:
    """(n,) complex64 -> (2, n) f32 re/im plane stack (traceable)."""
    return jnp.stack([jnp.real(amps), jnp.imag(amps)]).astype(jnp.float32)


def planes_to_complex(planes: jax.Array) -> jax.Array:
    """(2, n) f32 plane stack -> (n,) complex64 (traceable)."""
    return (planes[0] + 1j * planes[1]).astype(jnp.complex64)


_complex_to_planes = jax.jit(complex_to_planes)
_planes_to_complex = jax.jit(planes_to_complex)


def _complex_to_planes_batch(amps: jax.Array) -> jax.Array:
    """(L, n) complex64 -> (L, 2, n) f32 lane-batched plane stacks."""
    return jnp.stack([jnp.real(amps), jnp.imag(amps)],
                     axis=1).astype(jnp.float32)


def _planes_to_complex_batch(planes: jax.Array) -> jax.Array:
    """(L, 2, n) f32 lane-batched plane stacks -> (L, n) complex64."""
    return (planes[:, 0] + 1j * planes[:, 1]).astype(jnp.complex64)


_complex_to_planes_b = jax.jit(_complex_to_planes_batch)
_planes_to_complex_b = jax.jit(_planes_to_complex_batch)


class CodecBackend:
    """Where the block codec runs, as five phase hooks.

    ``fetch_group`` / ``store_group`` are the *host* halves (called from
    worker threads; GIL-friendly numpy/zlib only — they never touch JAX).
    The *device* halves run on the dispatch thread and are split at the
    blocking boundary:

    * ``stage_to_device``   — host staging -> device planes; dispatch only,
      never blocks.
    * ``dispatch_result``   — device planes -> an opaque in-flight *ticket*
      (the encode / plane→complex conversion is dispatched async here);
      never blocks.
    * ``await_result``      — ticket -> host result object; this is the
      ONLY blocking device wait in the pipeline, so the scheduler can park
      it in the in-flight window while later waves dispatch.  The
      pipeline blocks on the ticket's arrays first
      (``jax.block_until_ready``), so the hook times the d2h copy alone.

    ``fetch_result`` (dispatch + await back to back) remains as the
    convenience form for sequential callers.

    The host halves take the :class:`~repro.spans.StageRecord` of the
    stage they run for (``None`` outside a stage) and time their store
    and codec work as its ``store.get`` / ``codec.inflate`` and
    ``codec.deflate`` / ``store.put`` spans.

    Byte counters ``h2d_bytes`` / ``d2h_bytes`` accumulate the size of
    every array that crosses the host↔device boundary — the quantity the
    device-resident codec exists to shrink; ``n_raw_compressions`` counts
    the stored blocks that took the codec's RAW escape.  Phase hooks run
    concurrently on worker threads, so ALL counter updates are
    read-modify-write under ``_count_lock`` — use :meth:`add_bytes` /
    :meth:`add_counts`, never a bare ``+=``; read them with
    :meth:`counts`.

    Args:
        store: the two-level block store.
        params: pwrel bound shared by both codec halves.
        bsz: amplitudes per SV block (2^b, engine-constant).
        compression: False = raw complex64 blocks (Fig. 11 baseline).
        prescan: bitmap pre-scan RLE in the lossless stage (§4.3).
    """

    name: str = "abstract"

    def __init__(self, store: BlockStore, params: PwRelParams, bsz: int,
                 compression: bool = True, prescan: bool = True):
        self.store = store
        self.params = params
        self.bsz = bsz
        self.compression = compression
        self.prescan = prescan
        # phase hooks run in concurrent worker threads; counter updates
        # are read-modify-write, so the fields below may only be touched
        # inside 'with self._count_lock:' (lock-discipline checker) —
        # mutate through add_counts / add_bytes
        self.h2d_bytes = 0                     # guarded-by: _count_lock
        self.d2h_bytes = 0                     # guarded-by: _count_lock
        self.n_decompressions = 0              # guarded-by: _count_lock
        self.n_compressions = 0                # guarded-by: _count_lock
        self.n_raw_compressions = 0            # guarded-by: _count_lock
        self._count_lock = threading.Lock()

    def add_counts(self, decompressions: int = 0, compressions: int = 0,
                   raw: int = 0) -> None:
        with self._count_lock:
            self.n_decompressions += decompressions
            self.n_compressions += compressions
            self.n_raw_compressions += raw

    def counts(self) -> tuple[int, int, int, int, int]:
        """``(decompressions, compressions, raw compressions, h2d bytes,
        d2h bytes)`` so far, read together under the lock."""
        with self._count_lock:
            return (self.n_decompressions, self.n_compressions,
                    self.n_raw_compressions, self.h2d_bytes, self.d2h_bytes)

    def add_bytes(self, h2d: int = 0, d2h: int = 0) -> None:
        """Locked accumulation of the boundary byte ledger (hooks may run
        on several threads at once — a bare ``+=`` here loses updates)."""
        with self._count_lock:
            self.h2d_bytes += h2d
            self.d2h_bytes += d2h

    # -- host block codec (also used for init/collect outside the pipeline) --
    def encode_host_block(self, key: int, amps: np.ndarray,
                          rec: StageRecord | None = None) -> None:
        """Compress one np block on the host and store it under ``key``."""
        fault_point("codec.encode")
        if not self.compression:
            with span("store.put", rec):
                self.store.put(key, np.asarray(amps, np.complex64).tobytes())
            return
        with span("codec.deflate", rec):
            seg = encode_block_host(amps, self.params, prescan=self.prescan)
        with span("store.put", rec):
            self.store.put_block(key, seg)
        if seg.is_raw:
            self.add_counts(raw=1)

    def decode_host_block(self, key: int,
                          rec: StageRecord | None = None) -> np.ndarray:
        """Fetch the block under ``key`` and decompress it on the host."""
        fault_point("codec.decode")
        if not self.compression:
            with span("store.get", rec):
                return np.frombuffer(self.store.get(key), dtype=np.complex64)
        with span("store.get", rec):
            seg = self.store.get_block(key)
        with span("codec.inflate", rec):
            return decode_block_host(seg, self.params)

    # -- phase hooks ---------------------------------------------------------
    def fetch_group(self, block_ids: np.ndarray,
                    rec: StageRecord | None = None):
        """Worker thread: store -> host staging object for one group."""
        raise NotImplementedError

    def stage_to_device(self, staged, device) -> jax.Array:
        """Dispatch thread: host staging -> (2, 2^(b+m)) f32 device plane
        stack (async) — the stage compute's planes-resident input."""
        raise NotImplementedError

    def dispatch_result(self, planes_dev: jax.Array, n_blocks: int):
        """Dispatch thread: device plane stack -> in-flight ticket.  The
        device half of the encode is dispatched here (async); MUST NOT
        block."""
        raise NotImplementedError

    def await_result(self, ticket):
        """Dispatch thread: in-flight ticket -> host result object
        (blocks).  The pipeline's only blocking boundary wait."""
        raise NotImplementedError

    def fetch_result(self, planes_dev: jax.Array, n_blocks: int):
        """Dispatch + await back to back (sequential convenience form)."""
        return self.await_result(self.dispatch_result(planes_dev, n_blocks))

    def store_group(self, block_ids: np.ndarray, result,
                    rec: StageRecord | None = None) -> None:
        """Worker thread: host result object -> store."""
        raise NotImplementedError

    # -- row-batched phase hooks ---------------------------------------------
    #
    # ``key_rows`` is an (R, 2^m) store-key table: one row of block keys
    # per independent group instance.  The rows are *row-agnostic* — a
    # ``run_batch`` feeds L lanes of one group, the wave scheduler feeds
    # ``depth`` consecutive groups (or their lanes-x-groups product); the
    # hooks only see rows.  The generic implementations loop the
    # single-row hooks; backends override where one stacked transfer /
    # one kernel dispatch can cover the batch.

    def fetch_group_batch(self, key_rows: np.ndarray,
                          rec: StageRecord | None = None):
        """Worker thread: store -> host staging for all rows."""
        return [self.fetch_group(row, rec) for row in key_rows]

    def stage_to_device_batch(self, staged, device) -> jax.Array:
        """Dispatch thread: host staging -> (R, 2, 2^(b+m)) f32 plane
        stacks (async) — the batched stage compute's input."""
        return jnp.stack([self.stage_to_device(s, device) for s in staged])

    def dispatch_result_batch(self, planes_dev: jax.Array, n_blocks: int):
        """Dispatch thread: (R, 2, N) device planes -> in-flight ticket
        (async encode dispatch; MUST NOT block)."""
        return [self.dispatch_result(planes_dev[r], n_blocks)
                for r in range(planes_dev.shape[0])]

    def await_result_batch(self, ticket):
        """Dispatch thread: ticket -> per-row host result objects (the
        pipeline's blocking boundary wait)."""
        return [self.await_result(t) for t in ticket]

    def fetch_result_batch(self, planes_dev: jax.Array, n_blocks: int):
        """Dispatch + await back to back for a row batch."""
        return self.await_result_batch(
            self.dispatch_result_batch(planes_dev, n_blocks))

    def store_group_batch(self, key_rows: np.ndarray, results,
                          rec: StageRecord | None = None) -> None:
        """Worker thread: per-row host results -> store."""
        for row, res in zip(key_rows, results):
            self.store_group(row, res, rec)

    def split_store_batch(self, key_rows: np.ndarray, results) -> list:
        """Dispatch thread: a wave's awaited results -> independent
        ``(key_rows, results)`` store units, each a valid
        :meth:`store_group_batch` call, that the store pool may run at
        once.  The generic form keeps the wave whole; the codec backends
        split it per block."""
        return [(key_rows, results)]


class HostCodecBackend(CodecBackend):
    """Baseline: the full codec runs on the host (seed engine behavior).

    Raw 2^(b+m) complex64 group arrays cross the host↔device boundary in
    both directions.  Also the only backend usable with
    ``compression=False``.
    """

    name = "host"

    def fetch_group(self, block_ids, rec=None):
        # decode straight into one preallocated flat group array — no
        # per-group np.concatenate copy
        flat = np.empty(len(block_ids) * self.bsz, dtype=np.complex64)
        for i, bid in enumerate(block_ids):
            flat[i * self.bsz:(i + 1) * self.bsz] = \
                self.decode_host_block(int(bid), rec)
        self.add_counts(decompressions=len(block_ids))
        return flat

    def stage_to_device(self, staged, device):
        self.add_bytes(h2d=staged.nbytes)
        return _complex_to_planes(jax.device_put(jnp.asarray(staged), device))

    def dispatch_result(self, planes_dev, n_blocks):
        # complex64 is re-materialized on device (async dispatch); the
        # raw fetch blocks in await_result
        return _planes_to_complex(planes_dev)

    def await_result(self, ticket):
        out = np.asarray(ticket)                  # blocking wait
        self.add_bytes(d2h=out.nbytes)
        return out

    def store_group(self, block_ids, result, rec=None):
        blocks = np.asarray(result).reshape(len(block_ids), self.bsz)
        for i, bid in enumerate(block_ids):
            self.encode_host_block(int(bid), blocks[i], rec)
        self.add_counts(compressions=len(block_ids))

    # -- row-batched overrides: one stacked boundary crossing per wave --
    def fetch_group_batch(self, key_rows, rec=None):
        rows, n_blocks = key_rows.shape
        flat = np.empty((rows, n_blocks * self.bsz), dtype=np.complex64)
        for r, row in enumerate(key_rows):
            for i, bid in enumerate(row):
                flat[r, i * self.bsz:(i + 1) * self.bsz] = \
                    self.decode_host_block(int(bid), rec)
        self.add_counts(decompressions=key_rows.size)
        return flat

    def stage_to_device_batch(self, staged, device):
        self.add_bytes(h2d=staged.nbytes)
        return _complex_to_planes_b(jax.device_put(jnp.asarray(staged),
                                                   device))

    def dispatch_result_batch(self, planes_dev, n_blocks):
        return _planes_to_complex_b(planes_dev)   # async dispatch

    def await_result_batch(self, ticket):
        out = np.asarray(ticket)                  # blocking wait
        self.add_bytes(d2h=out.nbytes)
        return out                     # (R, 2^(b+m)) complex64

    # store_group_batch: the base per-row loop is already right — the
    # host encode is per-block CPU work with nothing to batch

    def split_store_batch(self, key_rows, results):
        # one unit per block: its key and its slice of the (R, 2^(b+m))
        # result (a view, no copy)
        bsz = self.bsz
        return [(key_rows[r:r + 1, i:i + 1],
                 results[r:r + 1, i * bsz:(i + 1) * bsz])
                for r in range(key_rows.shape[0])
                for i in range(key_rows.shape[1])]


class DeviceCodecBackend(CodecBackend):
    """Device-resident lossy codec: compressed wire crosses the boundary.

    Requires ``compression=True`` (the raw-block toggle has no device
    half — use :func:`make_backend`, which falls back to the host backend).
    RAW-escape blocks (incompressible data) degrade gracefully to a raw
    transfer for that block only.
    """

    name = "device"

    def __init__(self, store, params, bsz, compression=True, prescan=True):
        assert compression, "device codec backend requires compression=True"
        super().__init__(store, params, bsz, compression, prescan)

    def fetch_group(self, block_ids, rec=None):
        staged = []
        for bid in block_ids:
            fault_point("codec.decode")
            with span("store.get", rec):
                seg = self.store.get_block(int(bid))
            if seg.is_raw:
                staged.append(("raw", np.frombuffer(
                    seg.raw, dtype=np.complex64, count=seg.n_amps)))
            else:
                with span("codec.inflate", rec):
                    staged.append(("wire", segments_to_wire(seg)))
        self.add_counts(decompressions=len(staged))
        return staged

    # the wire staged here was fetched through fetch_group, whose
    # per-block fault_point covers the path
    # fault-covered: codec.decode
    def stage_to_device(self, staged, device):
        parts: list = [None] * len(staged)        # per block: (2, bsz) f32
        wire_idx = []
        for i, (kind, payload) in enumerate(staged):
            if kind == "raw":
                self.add_bytes(h2d=payload.nbytes)
                parts[i] = _complex_to_planes(
                    jax.device_put(jnp.asarray(payload), device))
            else:
                wire_idx.append(i)
        if wire_idx:
            # batched: 3 transfers + 1 decode dispatch for the whole group;
            # the decode lands directly on f32 planes — no complex detour
            blocks, moved = decode_blocks_planes(
                [staged[i][1] for i in wire_idx], self.bsz, self.params,
                device)
            self.add_bytes(h2d=moved)
            for j, i in enumerate(wire_idx):
                parts[i] = blocks[j]
        return (jnp.concatenate(parts, axis=1) if len(parts) > 1
                else parts[0])

    # store_group fires the per-block fault_point on the same encoded
    # wire before it persists
    # fault-covered: codec.encode
    def dispatch_result(self, planes_dev, n_blocks):
        # the quantize/pack kernels launch here (async); only the wire
        # fetch in await_result blocks
        return encode_group_planes(planes_dev, n_blocks, self.params)

    def await_result(self, ticket):  # fault-covered: codec.encode
        wire, moved = fetch_group_wire(ticket)    # blocks until done
        self.add_bytes(d2h=moved)
        return wire

    def store_group(self, block_ids, result, rec=None):
        raw = 0
        for pair, bid in zip(result, block_ids):
            fault_point("codec.encode")
            with span("codec.deflate", rec):
                seg = wire_to_segments(pair, self.bsz, prescan=self.prescan,
                                       params=self.params)
            with span("store.put", rec):
                self.store.put_block(int(bid), seg)
            raw += seg.is_raw
        self.add_counts(compressions=len(block_ids), raw=raw)

    # -- row-batched overrides: every row's wire shares one codec
    # dispatch (the per-call decode/encode launch is the dominant cost on
    # a dispatch-bound config, so R rows must not pay it R times) --------
    # fault-covered: codec.decode — batched sibling of stage_to_device
    def stage_to_device_batch(self, staged, device):
        parts = [[None] * len(row) for row in staged]
        wire, where = [], []
        for r, row in enumerate(staged):
            for i, (kind, payload) in enumerate(row):
                if kind == "raw":
                    self.add_bytes(h2d=payload.nbytes)
                    parts[r][i] = _complex_to_planes(
                        jax.device_put(jnp.asarray(payload), device))
                else:
                    wire.append(payload)
                    where.append((r, i))
        if wire:
            blocks, moved = decode_blocks_planes(
                wire, self.bsz, self.params, device)
            self.add_bytes(h2d=moved)
            for j, (r, i) in enumerate(where):
                parts[r][i] = blocks[j]
        return jnp.stack([
            jnp.concatenate(row, axis=1) if len(row) > 1 else row[0]
            for row in parts])

    # fault-covered: codec.encode — batched sibling of dispatch_result
    def dispatch_result_batch(self, planes_dev, n_blocks):
        rows = planes_dev.shape[0]
        # row-major block order: (R, 2, N) -> (2, R*N), so one encode
        # dispatch covers every row's blocks and the wire list splits
        # back per row in await_result_batch
        flat = jnp.transpose(planes_dev, (1, 0, 2)).reshape(2, -1)
        encoded = encode_group_planes(flat, rows * n_blocks, self.params)
        return (encoded, rows, n_blocks)

    def await_result_batch(self, ticket):  # fault-covered: codec.encode
        encoded, rows, n_blocks = ticket
        wire, moved = fetch_group_wire(encoded)   # blocks until done
        self.add_bytes(d2h=moved)
        return [wire[r * n_blocks:(r + 1) * n_blocks]
                for r in range(rows)]

    def split_store_batch(self, key_rows, results):
        # one unit per block: its key and its wire pair
        return [(key_rows[r:r + 1, i:i + 1], [row[i:i + 1]])
                for r, row in enumerate(results)
                for i in range(key_rows.shape[1])]


def make_backend(name: str, store: BlockStore, params: PwRelParams,
                 bsz: int, compression: bool = True,
                 prescan: bool = True) -> CodecBackend:
    """Resolve an ``EngineConfig.codec_backend`` name to a backend.

    ``"device"`` degrades to ``"host"`` (with a ``RuntimeWarning``) when
    ``compression`` is off — there is no device half to a raw byte copy.
    """
    if name == "device" and compression:
        return DeviceCodecBackend(store, params, bsz, compression, prescan)
    if name == "device":
        warnings.warn(
            "codec_backend='device' requires compression=True; "
            "falling back to the host codec backend",
            RuntimeWarning, stacklevel=2)
    if name in ("host", "device"):
        return HostCodecBackend(store, params, bsz, compression, prescan)
    raise ValueError(f"unknown codec backend {name!r} "
                     "(expected 'host' or 'device')")


#: fetch lookahead beyond the wave being computed (waves, not groups):
#: one decoding while one is staged is the double buffer; more only adds
#: host staging memory without hiding additional latency
_FETCH_LOOKAHEAD = 2

#: in-flight results: wave w's blocking await runs only after wave w+1's
#: compute + encode have been dispatched (the double-buffered boundary)
_INFLIGHT_WINDOW = 2


class StagePipeline:
    """Orchestrates the per-group load → compute → store loop of a stage.

    ``depth`` is the wave width: ``depth`` consecutive groups coalesce
    into one row-batched dispatch through the backend's ``*_batch`` hooks,
    and up to two waves are in flight at once — wave *w*'s blocking
    device→host wait (``await_result``) runs *after* wave *w+1*'s compute
    and encode dispatches, so it hides under device work, while the host
    codec halves run on the fetch/store worker pools behind a completion
    ready-queue, the store pool one task per block (see the module docs).
    ``depth=1`` is the strictly sequential reference schedule.

    Use as a context manager (owns the worker pools); call
    :meth:`run_stage` once per partition stage.  Each call returns the
    stage's :class:`~repro.spans.StageRecord` (also kept in the
    ``repro.spans`` ring under run number ``run``): its spans time the
    phases on every thread (see :mod:`repro.spans`), its counters the
    blocks and bytes the stage moved.

    ``n_group_phases`` counts group×stage phase executions — the
    denominator that turns the summed phase spans into the per-group
    :class:`~repro.core.planner.PipelineCalibration` the planner's
    depth model consumes.
    """

    def __init__(self, backend: CodecBackend, depth: int = 2,
                 devices: list | None = None,
                 fetch_workers: int | None = None,
                 run: int | None = None):
        self.backend = backend
        #: the run number the stage records carry (a new one if None)
        self.run = next_run() if run is None else run
        self.depth = max(1, depth)
        self.devices = devices or [jax.devices()[0]]
        # fetch pool width.  None = adaptive: one worker per spare core,
        # capped at the lookahead — and NO pools at all on a single-core
        # host, where waves still coalesce but run on the caller's
        # thread (extra decode threads only thrash the dispatch thread's
        # GIL slice).  An explicit >= 1 forces the threaded overlap
        # scheduler regardless of core count (the overlap tests use
        # this); an explicit 0 forces the coalescing-only wave loop.
        self.fetch_workers = fetch_workers
        #: in-flight result window (double buffer).  An instance attr —
        #: not the module constant — so the pressure ladder can shrink it
        #: to 1 between stages (rung 1) without rebuilding the pools.
        self.inflight_window = _INFLIGHT_WINDOW
        self.n_group_phases = 0
        #: device id -> group rows its waves ran (lane rows count apiece)
        self.rows_per_device: dict[int, int] = {}
        self._dec_pool: ThreadPoolExecutor | None = None
        self._com_pool: ThreadPoolExecutor | None = None
        # store calls running now, for the stage record's
        # store_peak_inflight
        self._stores_running = 0               # guarded-by: _stores_lock
        self._stores_lock = threading.Lock()
        self._entered = False

    def __enter__(self) -> "StagePipeline":
        # the threaded overlap scheduler only engages when spare cores
        # exist to run the workers (or the caller forces a pool width):
        # on a single-core host the context switches and GIL handoffs
        # cost more than the overlap hides, and wave *coalescing* — the
        # actual dispatch-amortization win — doesn't need threads.  The
        # fetch pool is lookahead-wide so the ready-queue can consume
        # waves in completion order (a slow decode never serializes the
        # loop); the store pool takes every core the dispatch thread and
        # the fetch workers leave, so a wave's per-block deflates run at
        # once (its threads start only as tasks queue up).
        if self.depth > 1:
            cores = os.cpu_count() or 1
            nw = self.fetch_workers
            if nw is None and cores > 1:
                nw = min(_FETCH_LOOKAHEAD, cores - 1)
            if nw:
                self._dec_pool = ThreadPoolExecutor(max_workers=nw)
                self._com_pool = ThreadPoolExecutor(
                    max_workers=max(1, cores - 1 - nw))
        self._entered = True
        return self

    def __exit__(self, *exc) -> None:
        if self._dec_pool is not None:
            self._dec_pool.shutdown(wait=True)
            self._com_pool.shutdown(wait=True)
        self._dec_pool = self._com_pool = None
        self._entered = False

    # -- phase wrappers (the host halves run inside worker threads) -----------
    @staticmethod
    def _key_context(keys) -> dict:
        """The :class:`StoreIOError` arguments that name ``keys``: the
        key itself for one block, else the span of the wave's keys."""
        flat = np.asarray(keys).reshape(-1)
        if flat.size == 0:
            return {"detail": "no keys"}
        if flat.size == 1:
            return {"key": int(flat[0])}
        return {"detail": f"keys [{int(flat.min())}..{int(flat.max())}] "
                          f"({flat.size} blocks)"}

    def _load(self, fetch, keys, rec):
        with span("pipeline.load", rec):
            try:
                fault_point("pipeline.fetch")
                return fetch(keys, rec)
            except (StoreIOError, BlockCorruptionError):
                raise               # already typed with key/blob context
            except OSError as e:
                # a raw OSError escaping a fetch worker carries no context
                # — name the wave so the failure is attributable
                raise StoreIOError("pipeline fetch",
                                   **self._key_context(keys)) from e

    def _store(self, store, keys, result, rec):
        with self._stores_lock:
            self._stores_running += 1
            rec.store_peak_inflight = max(rec.store_peak_inflight,
                                          self._stores_running)
        try:
            with span("pipeline.store", rec):
                try:
                    fault_point("pipeline.store")
                    store(keys, result, rec)
                except (StoreIOError, BlockCorruptionError):
                    raise
                except OSError as e:
                    raise StoreIOError("pipeline store",
                                       **self._key_context(keys)) from e
        finally:
            with self._stores_lock:
                self._stores_running -= 1

    def _dispatch(self, wave_fn, staged, dev, imats, n_blocks, rec):
        """Dispatch thread: h2d staging, the stage program and the encode
        dispatch of one wave (async; never a device wait)."""
        back = self.backend
        with span("pipeline.dispatch", rec):
            planes = back.stage_to_device_batch(staged, dev)
            out = wave_fn(planes, *imats)
            return back.dispatch_result_batch(out, n_blocks)

    def _await(self, await_, ticket, rec):
        """Dispatch thread: the blocking wait for a wave's result, in two
        spans: until the device has computed it, then its d2h copy."""
        with span("pipeline.device_wait", rec):
            jax.block_until_ready(ticket)
        with span("pipeline.d2h_wait", rec):
            return await_(ticket)

    def _device_for(self, w: int):
        return self.devices[w % len(self.devices)]

    def run_stage(self, block_ids: np.ndarray, fn, mats,
                  lane_offsets: np.ndarray | None = None,
                  wave_fn=None, lane_shards=None,
                  group_devices=None, stage: int = 0) -> StageRecord:
        """Run one stage: ``block_ids`` is the (n_groups, 2^m) layout table,
        ``fn`` the jitted single-group update function, ``mats`` its
        operands, ``stage`` the stage's index in the plan.  Returns the
        stage's record.

        ``wave_fn`` is the row-batched form of the stage update ((R, 2,
        2^(b+m)) planes -> same; operands broadcast/tiled in-trace) — it
        enables the wave-coalesced scheduler.  Without it (the legacy
        per-gate path has no batched form) the stage runs strictly
        sequentially through the single-group hooks.

        ``lane_offsets`` switches on the lane-batched path: each wave's
        key table stacks ``lane_offsets[:, None] + block_ids[g]`` for the
        wave's groups (groups-major), and ``wave_fn`` updates the
        (depth·L, 2, 2^(b+m)) row stack in one dispatch.

        Multi-device placement (one of):

        * ``lane_shards`` — ``[(device, lane_slice), ...]``: each wave
          splits into one item per shard, carrying that shard's lane
          rows (keys from ``lane_offsets[lane_slice]``) and its slice of
          the lane-stacked operands, pre-placed on the shard's device.
          Shards touch disjoint store-key ranges, so there is nothing to
          exchange — the near-linear tier.
        * ``group_devices`` — per-group device (the plan's
          ``device_slot`` placement): the stage's groups are bucketed by
          device, chunked into depth-wide waves, and interleaved so
          consecutive dispatches land on different devices and overlap
          under async dispatch.  The engine accounts the blocks whose
          owner changed since the previous stage (compressed-wire
          exchange).

        Both default to the single-device schedule when absent.
        """
        assert self._entered, "use StagePipeline as a context manager"
        n_groups, n_blocks = block_ids.shape
        self.n_group_phases += n_groups
        back = self.backend
        lanes = 1 if lane_offsets is None else len(lane_offsets)
        before = back.counts()
        with stage_record("stage", self.run, stage, lanes) as rec:
            rec.groups = n_groups
            if wave_fn is None:
                # legacy per-gate path: no batched form to shard a wave
                # with, but _run_sequential_single already places group g
                # on devices[g % D] — the same round-robin the plan's
                # device_slot records
                rec.waves = n_groups
                self._run_sequential_single(block_ids, fn, mats,
                                            lane_offsets, rec)
            else:
                items = self._wave_items(block_ids, mats, lane_offsets,
                                         lane_shards, group_devices)
                rec.waves = len(items)
                for keys, dev, _ in items:
                    self.rows_per_device[dev.id] = \
                        self.rows_per_device.get(dev.id, 0) + len(keys)
                if self._dec_pool is None:
                    self._run_waves(items, wave_fn, n_blocks, rec)
                else:
                    self._run_overlapped(items, wave_fn, n_blocks, rec)
            (rec.blocks_loaded, rec.blocks_stored, rec.raw_blocks_stored,
             rec.h2d_bytes, rec.d2h_bytes) = (
                b - a for a, b in zip(before, back.counts()))
            if back.store is not None:
                rec.stored_bytes = back.store.total_bytes
        return rec

    # -- wave item construction ----------------------------------------------
    def _wave_items(self, block_ids, mats, lane_offsets, lane_shards,
                    group_devices):
        """Flatten one stage into ``(key_rows, device, operands)`` wave
        items — the unit both schedulers consume.  Operands are placed on
        their item's device once per stage (committed arrays), so the
        jitted wave fn runs where its planes live instead of dragging
        uncommitted operands across the mesh on every dispatch."""
        n_groups, _ = block_ids.shape
        W = min(self.depth, n_groups)

        def lane_keys(gids, offs):
            return np.concatenate(
                [offs[:, None] + row[None, :] for row in gids])

        items = []
        if lane_shards:
            shard_ops = [
                (dev, sl, tuple(jax.device_put(m[sl], dev) for m in mats))
                for dev, sl in lane_shards]
            for lo in range(0, n_groups, W):
                gids = block_ids[lo:lo + W]
                for dev, sl, smats in shard_ops:
                    items.append((lane_keys(gids, lane_offsets[sl]),
                                  dev, smats))
            return items
        if group_devices is not None:
            # bucket groups by their slot device, chunk each bucket into
            # depth-wide waves, and interleave one chunk per device so
            # consecutive dispatches overlap across the mesh
            buckets: dict[int, list[int]] = {}
            order = []
            for g, dev in enumerate(group_devices):
                k = id(dev)
                if k not in buckets:
                    buckets[k] = []
                    order.append((k, dev))
                buckets[k].append(g)
            dev_mats = {k: tuple(jax.device_put(m, dev) for m in mats)
                        for k, dev in order}
            chunks = {k: [buckets[k][i:i + W]
                          for i in range(0, len(buckets[k]), W)]
                      for k, _ in order}
            while any(chunks[k] for k, _ in order):
                for k, dev in order:
                    if not chunks[k]:
                        continue
                    gids = block_ids[np.asarray(chunks[k].pop(0))]
                    keys = (gids if lane_offsets is None
                            else lane_keys(gids, lane_offsets))
                    items.append((keys, dev, dev_mats[k]))
            return items
        for w, lo in enumerate(range(0, n_groups, W)):
            gids = block_ids[lo:lo + W]
            keys = (gids if lane_offsets is None
                    else lane_keys(gids, lane_offsets))
            items.append((keys, self._device_for(w), mats))
        return items

    @staticmethod
    def _window_for(items, base: int) -> int:
        """In-flight window of a wave-item schedule: at least one item
        per distinct device, so a multi-device stage keeps every device
        busy while older waves drain at the boundary."""
        n_dev = len({id(dev) for _, dev, _ in items})
        if n_dev <= 1:
            return base
        return max(base, min(n_dev, len(items)))

    # -- sequential wave loop (depth 1 / coalescing-only hosts) ---------------
    def _run_waves(self, items, wave_fn, n_blocks, rec) -> None:
        """Caller's-thread wave loop: no pools, no lookahead.  On one
        device the window is 1 — the strictly sequential reference
        schedule.  With several devices the window widens to the device
        count: each device's compute is dispatched (async) before any
        older wave's blocking boundary wait, so the mesh overlaps even
        without worker threads."""
        back = self.backend
        window = self._window_for(items, 1)
        in_flight: deque = deque()

        def drain():
            okeys, oticket = in_flight.popleft()
            result = self._await(back.await_result_batch, oticket, rec)
            self._store(back.store_group_batch, okeys, result, rec)

        for keys, dev, imats in items:
            staged = self._load(back.fetch_group_batch, keys, rec)
            ticket = self._dispatch(wave_fn, staged, dev, imats, n_blocks,
                                    rec)
            in_flight.append((keys, ticket))
            if len(in_flight) >= window:
                drain()
        while in_flight:
            drain()

    # -- strictly sequential fallback (no batched stage fn) -------------------
    def _run_sequential_single(self, block_ids, fn, mats, lane_offsets,
                               rec):
        """Legacy per-gate path: one group per dispatch, in order, on the
        caller's thread (kept for the side-by-side benchmark — it has no
        row-batched stage function to coalesce waves with)."""
        back = self.backend
        n_groups, n_blocks = block_ids.shape
        if lane_offsets is None:
            fetch, to_dev = back.fetch_group, back.stage_to_device
            dispatch, await_ = back.dispatch_result, back.await_result
            store = back.store_group
            group_keys = [block_ids[g] for g in range(n_groups)]
        else:
            fetch, to_dev = back.fetch_group_batch, back.stage_to_device_batch
            dispatch, await_ = (back.dispatch_result_batch,
                                back.await_result_batch)
            store = back.store_group_batch
            group_keys = [lane_offsets[:, None] + block_ids[g][None, :]
                          for g in range(n_groups)]
        for g in range(n_groups):
            staged = self._load(fetch, group_keys[g], rec)
            with span("pipeline.dispatch", rec):
                amps_dev = to_dev(staged, self._device_for(g))
                out = fn(amps_dev, *mats)
                ticket = dispatch(out, n_blocks)
            result = self._await(await_, ticket, rec)
            self._store(store, group_keys[g], result, rec)

    # -- the double-buffered wave loop ---------------------------------------
    def _run_overlapped(self, items, wave_fn, n_blocks, rec) -> None:
        back = self.backend
        n_waves = len(items)
        window = self._window_for(items, self.inflight_window)
        ready: queue.SimpleQueue = queue.SimpleQueue()
        outstanding: dict[int, object] = {}
        submitted = 0

        def submit_next():
            nonlocal submitted
            if submitted < n_waves:
                w = submitted
                submitted += 1
                fut = self._dec_pool.submit(self._load,
                                            back.fetch_group_batch,
                                            items[w][0], rec)
                outstanding[w] = fut
                fut.add_done_callback(lambda _f, w=w: ready.put(w))

        in_flight: deque = deque()     # (wave, ticket) dispatched, unawaited
        pending_save = []

        def save_oldest():
            ow, oticket = in_flight.popleft()
            result = self._await(back.await_result_batch, oticket, rec)
            # one store task per block: the pool deflates them at once
            for keys, unit in back.split_store_batch(items[ow][0], result):
                pending_save.append(self._com_pool.submit(
                    self._store, back.store_group_batch, keys, unit, rec))

        try:
            for _ in range(min(1 + _FETCH_LOOKAHEAD, n_waves)):
                submit_next()
            for _ in range(n_waves):
                # completion-order ready-queue: take whichever lookahead
                # fetch finished first — a slow decode never serializes
                # the loop behind wave order
                with span("pipeline.load_wait", rec):
                    w = ready.get()
                    staged = outstanding.pop(w).result()
                _, dev, imats = items[w]
                ticket = self._dispatch(wave_fn, staged, dev, imats,
                                        n_blocks, rec)
                submit_next()          # keep the fetch lookahead full
                in_flight.append((w, ticket))
                if len(in_flight) >= window:
                    # double buffer: wave w is computing asynchronously
                    # while this (older) wave's blocking wait drains
                    save_oldest()
            while in_flight:           # drain the window
                save_oldest()
        except BaseException:
            # fail fast without deadlocking the pools: drop queued
            # fetches, let running ones finish (shutdown waits), and
            # surface the ORIGINAL error over any secondary store failure
            for fut in outstanding.values():
                fut.cancel()
            for fut in pending_save:
                try:
                    fut.result()
                except Exception:  # lint: disable=typed-errors -- keep original error
                    pass
            raise
        with span("pipeline.store_wait", rec):
            # stage barrier (§4.1 semantics): every block's store ends
            # before the stage returns, then the first failure raises
            wait(pending_save)
            for fut in pending_save:
                fut.result()
