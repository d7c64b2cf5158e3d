"""Deterministic overlap tests for the double-buffered wave scheduler.

These tests drive :class:`StagePipeline` directly with a synthetic codec
backend that records a timestamped phase-event trace and gates individual
phases on :class:`threading.Event` objects.  NO assertion in this file
depends on wall-clock timing or ``time.sleep`` — overlap is proven by
trace *order* (which phases the scheduler interleaved) and by event gates
that would deadlock a sequential schedule; every ``Event.wait`` uses a
generous timeout whose expiry is converted into a test failure, never a
hang.

Scheduler guarantees under test (see pipeline.py's module docs):

* depth >= 2: wave w's blocking ``await_result_batch`` runs only AFTER
  wave w+1's compute/encode ``dispatch_result_batch`` (the in-flight
  window) and after wave w+2's fetch has been submitted (the lookahead).
* depth == 1: strictly sequential fetch -> stage -> dispatch -> await ->
  store per wave, on the caller's thread, in wave order.
* the completion ready-queue consumes fetches in *completion* order — a
  slow decode does not serialize the waves behind it.
* ``run_stage`` returns only after every store future has drained (the
  stage barrier), and a fetch exception propagates out of ``run_stage``
  without deadlocking the pools.
* the backend byte/count ledgers are exact under concurrent phase hooks.
"""
import threading

import numpy as np
import pytest

from repro.core.pipeline import CodecBackend, HostCodecBackend, StagePipeline

_TIMEOUT = 10.0          # failsafe only — expiry == test failure, not a hang


def _wait(event: threading.Event, what: str) -> None:
    assert event.wait(_TIMEOUT), \
        f"expected overlap did not happen: timed out waiting for {what}"


class RecordingBackend(CodecBackend):
    """Synthetic codec backend: an in-memory dict of float values keyed by
    block id, a thread-safe ``(phase, wave_first_key)`` trace, and optional
    per-phase event gates.

    The wave scheduler only touches the ``*_batch`` hooks (plus
    ``add_bytes``/``add_counts``), so nothing here imports JAX — "device
    planes" are plain numpy arrays and the stage function is whatever the
    test passes as ``wave_fn``.
    """

    name = "recording"

    def __init__(self, n_keys: int):
        super().__init__(store=None, params=None, bsz=1)
        self.data = {k: float(k) for k in range(n_keys)}
        self._data_lock = threading.Lock()
        self.trace: list[tuple[str, int]] = []
        self._trace_lock = threading.Lock()
        # {phase-name: {wave_first_key: Event}} — the hook blocks on the
        # event before doing its work (failsafe timeout -> test failure)
        self.gates: dict[str, dict[int, threading.Event]] = {}
        # {phase-name: {wave_first_key: Event}} — set when the hook runs,
        # so tests (or other gates) can sequence on phase entry
        self.reached: dict[str, dict[int, threading.Event]] = {}
        # {wave_first_key: exception} raised from fetch_group_batch
        self.fetch_raises: dict[int, BaseException] = {}

    # -- instrumentation ------------------------------------------------------
    def gate(self, phase: str, wid: int) -> threading.Event:
        ev = threading.Event()
        self.gates.setdefault(phase, {})[wid] = ev
        return ev

    def reached_event(self, phase: str, wid: int) -> threading.Event:
        ev = threading.Event()
        self.reached.setdefault(phase, {})[wid] = ev
        return ev

    def _enter(self, phase: str, wid: int) -> None:
        ev = self.reached.get(phase, {}).get(wid)
        if ev is not None:
            ev.set()
        gate = self.gates.get(phase, {}).get(wid)
        if gate is not None:
            _wait(gate, f"gate on {phase}[wave {wid}]")
        with self._trace_lock:
            self.trace.append((phase, wid))

    def index(self, phase: str, wid: int) -> int:
        """Trace index of the (unique) ``(phase, wid)`` event."""
        hits = [i for i, e in enumerate(self.trace) if e == (phase, wid)]
        assert len(hits) == 1, f"{(phase, wid)} appeared {len(hits)}x"
        return hits[0]

    # -- batch phase hooks (all the wave scheduler calls) ---------------------
    def fetch_group_batch(self, key_rows, rec=None):
        wid = int(key_rows[0, 0])
        self._enter("fetch", wid)
        exc = self.fetch_raises.get(wid)
        if exc is not None:
            raise exc
        with self._data_lock:
            staged = np.array([[self.data[int(k)] for k in row]
                               for row in key_rows])
        self.add_counts(decompressions=key_rows.size)
        return (wid, staged)

    def stage_to_device_batch(self, staged, device):
        wid, arr = staged
        self._enter("stage", wid)
        self.add_bytes(h2d=arr.nbytes)
        return (wid, arr)

    def dispatch_result_batch(self, planes_dev, n_blocks):
        wid, arr = planes_dev
        self._enter("dispatch", wid)
        return (wid, arr)

    def await_result_batch(self, ticket):
        wid, arr = ticket
        self._enter("await", wid)
        self.add_bytes(d2h=arr.nbytes)
        return (wid, arr)

    def store_group_batch(self, key_rows, results, rec=None):
        wid, arr = results
        self._enter("store", wid)
        with self._data_lock:
            for row, vals in zip(key_rows, arr):
                for k, v in zip(row, vals):
                    self.data[int(k)] = float(v)
        self.add_counts(compressions=key_rows.size)
        self._enter("store_done", wid)


def _double(planes, *mats):
    wid, arr = planes
    return (wid, arr * 2.0)


def _run(backend: RecordingBackend, depth: int, n_groups: int,
         n_blocks: int = 2, **pipe_kw) -> None:
    # force the threaded overlap scheduler: the adaptive default builds
    # no pools on a single-core host (CI containers), and these tests
    # assert the *overlapped* schedule, not the coalescing-only one
    pipe_kw.setdefault("fetch_workers", 1)
    block_ids = np.arange(n_groups * n_blocks).reshape(n_groups, n_blocks)
    pipe = StagePipeline(backend, depth=depth, **pipe_kw)
    with pipe:
        pipe.run_stage(block_ids, fn=None, mats=[], wave_fn=_double)


def test_depth1_is_strictly_sequential():
    back = RecordingBackend(8)
    _run(back, depth=1, n_groups=4)
    expected = [(ph, 2 * g)
                for g in range(4)
                for ph in ("fetch", "stage", "dispatch", "await",
                           "store", "store_done")]
    assert back.trace == expected
    assert back.data == {k: 2.0 * k for k in range(8)}


def test_coalescing_only_mode_is_sequential_over_waves():
    # fetch_workers=0 (and the adaptive default on a single-core host)
    # keeps the wave coalescing but drops the worker pools: waves run
    # strictly sequentially on the caller's thread, one batched hook
    # call per phase per wave
    back = RecordingBackend(16)
    _run(back, depth=2, n_groups=8, fetch_workers=0)
    expected = [(ph, 4 * w)                       # wave ids 0, 4, 8, 12
                for w in range(4)
                for ph in ("fetch", "stage", "dispatch", "await",
                           "store", "store_done")]
    assert back.trace == expected
    assert back.data == {k: 2.0 * k for k in range(16)}


@pytest.mark.parametrize("depth", [2, 4])
def test_deeper_waves_dispatch_before_older_await(depth):
    # 4 waves; wave ids (first store key) are 0, 2W, 4W, 6W for n_blocks=2
    back = RecordingBackend(8 * depth)
    wids = [2 * depth * w for w in range(4)]
    # deterministically pin the fetch lookahead: wave w's blocking await
    # does not proceed until wave w+2's fetch has entered.  The scheduler
    # submits the lookahead before awaiting, so the gate clears on a pool
    # worker; a scheduler without the lookahead would time out (= fail).
    for w in range(2):
        back.gates.setdefault("await", {})[wids[w]] = \
            back.reached_event("fetch", wids[w + 2])
    _run(back, depth=depth, n_groups=4 * depth)
    for w in range(3):
        # the in-flight window: wave w is awaited only after wave w+1's
        # compute has been dispatched — the headline overlap property
        assert back.index("dispatch", wids[w + 1]) \
            < back.index("await", wids[w])
    for w in range(2):
        assert back.index("fetch", wids[w + 2]) < back.index("await", wids[w])
    assert back.data == {k: 2.0 * k for k in range(8 * depth)}


def test_await_gated_on_next_dispatch_does_not_deadlock():
    # stronger, event-gated form of the overlap property: wave 0's await
    # BLOCKS until wave 1's dispatch has happened.  A sequential schedule
    # (await w before dispatch w+1) would time out here; the overlapped
    # scheduler satisfies the gate on its own thread before awaiting.
    back = RecordingBackend(8)
    back.gates.setdefault("await", {})[0] = \
        back.reached_event("dispatch", 4)     # wave 1 first key = 4
    _run(back, depth=2, n_groups=4)
    assert back.index("dispatch", 4) < back.index("await", 0)
    assert back.data == {k: 2.0 * k for k in range(8)}


def test_ready_queue_consumes_fetches_in_completion_order():
    # Make wave 0 the slow decode: its fetch blocks until the compute
    # loop has already begun *staging* wave 1 — i.e. until the ready
    # queue has delivered wave 1 first.  With a lookahead-wide fetch pool
    # (forced explicitly: the adaptive default is 1 worker on a 1-core
    # host) both fetches are in flight at once, so the gate clears and
    # the loop computes wave 1 before wave 0 despite submission order; a
    # scheduler that insisted on wave order would time out (= fail).
    back = RecordingBackend(8)
    back.gates.setdefault("fetch", {})[0] = back.reached_event("stage", 4)
    _run(back, depth=2, n_groups=4, fetch_workers=2)
    assert back.index("dispatch", 4) < back.index("dispatch", 0)
    # correctness is unaffected by the reordering
    assert back.data == {k: 2.0 * k for k in range(8)}


def test_stage_barrier_drains_every_store_future():
    back = RecordingBackend(16)
    _run(back, depth=4, n_groups=8)          # 2 waves of 4 groups
    done = [e for e in back.trace if e[0] == "store_done"]
    assert len(done) == 2                     # every wave's store finished
    assert back.data == {k: 2.0 * k for k in range(16)}


def test_fetch_exception_propagates_without_deadlock():
    class Boom(RuntimeError):
        pass

    back = RecordingBackend(32)
    back.fetch_raises[16] = Boom("injected fetch failure")   # wave 2 of 4
    block_ids = np.arange(32).reshape(16, 2)
    pipe = StagePipeline(back, depth=4, fetch_workers=1)
    with pytest.raises(Boom, match="injected fetch failure"):
        with pipe:
            pipe.run_stage(block_ids, fn=None, mats=[], wave_fn=_double)
    # the context exited cleanly (pools shut down) and the failing wave
    # never reached the store
    assert pipe._dec_pool is None and pipe._com_pool is None
    assert ("store", 16) not in back.trace
    # a fresh pipeline on the same backend still works (no poisoned state)
    back.fetch_raises.clear()
    back.trace.clear()
    back.data = {k: float(k) for k in range(32)}
    _run(back, depth=4, n_groups=16)
    assert back.data == {k: 2.0 * k for k in range(32)}


# -- byte/count ledger under concurrency -------------------------------------

def test_byte_ledger_exact_under_concurrent_add_bytes():
    back = RecordingBackend(1)
    n_threads, n_iter = 8, 2000
    start = threading.Barrier(n_threads)

    def hammer():
        start.wait()
        for _ in range(n_iter):
            back.add_bytes(h2d=3, d2h=7)
            back.add_counts(decompressions=1, compressions=2)

    threads = [threading.Thread(target=hammer) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert back.h2d_bytes == 3 * n_threads * n_iter
    assert back.d2h_bytes == 7 * n_threads * n_iter
    assert back.n_decompressions == n_threads * n_iter
    assert back.n_compressions == 2 * n_threads * n_iter


def test_host_backend_ledger_exact_under_concurrent_phase_hooks():
    """Run the REAL host backend's staged/await hooks from many threads at
    once and check the byte ledger to the exact byte — the regression test
    for the unlocked ``+=`` the hooks used to do."""
    jax = pytest.importorskip("jax")
    from repro.compression.pwrel import PwRelParams
    from repro.compression.store import BlockStore

    bsz = 32
    back = HostCodecBackend(BlockStore(), PwRelParams(), bsz)
    rng = np.random.default_rng(7)
    amps = (rng.standard_normal(bsz) + 1j * rng.standard_normal(bsz)) \
        .astype(np.complex64)
    back.encode_host_block(0, amps)
    dev = jax.devices()[0]
    n_threads, n_iter = 4, 16
    start = threading.Barrier(n_threads)
    errors: list[BaseException] = []

    def worker():
        try:
            start.wait()
            keys = np.zeros(1, dtype=np.int64)
            for _ in range(n_iter):
                staged = back.fetch_group(keys)
                planes = back.stage_to_device(staged, dev)
                back.await_result(back.dispatch_result(planes, 1))
        except BaseException as e:      # pragma: no cover - failure path
            errors.append(e)

    threads = [threading.Thread(target=worker) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    per_xfer = bsz * 8                  # complex64 both ways on host backend
    assert back.h2d_bytes == per_xfer * n_threads * n_iter
    assert back.d2h_bytes == per_xfer * n_threads * n_iter
    assert back.n_decompressions == n_threads * n_iter


# -- per-block store fan-out --------------------------------------------------
#
# The overlapped scheduler submits one store task per block of an awaited
# wave (``split_store_batch``), and the store pool runs them at once.  The
# reference is the strictly sequential depth-1 schedule, which stores one
# group a call on the caller's thread.

def _fan_out_run(monkeypatch, backend, batched, depth, fetch_workers,
                 rendezvous=False):
    """Run qft-10 (16 groups of 4 blocks) on a Simulator whose pipelines
    use ``fetch_workers``; returns (lane states, stored blobs, stage
    records, the set of block counts the store calls took).
    ``rendezvous`` makes the first two store calls of the run wait for
    each other, so they must run at once."""
    import functools

    from repro import EngineConfig, Simulator, build_circuit, spans
    from repro.core import engine as engine_mod
    from repro.core import pipeline as pipeline_mod

    monkeypatch.setattr(
        engine_mod, "StagePipeline",
        functools.partial(StagePipeline, fetch_workers=fetch_workers))
    # a store pool at least two wide on any host: 8 cores less the
    # dispatch thread and the fetch workers
    monkeypatch.setattr(pipeline_mod.os, "cpu_count", lambda: 8)
    cls = (pipeline_mod.DeviceCodecBackend if backend == "device"
           else HostCodecBackend)
    meet = threading.Barrier(2, timeout=_TIMEOUT)
    calls = iter(range(2 if rendezvous else 0))
    sizes = set()
    real = cls.store_group

    def store_group(self, block_ids, result, rec=None):
        sizes.add(len(block_ids))
        if next(calls, None) is not None:
            meet.wait()              # BrokenBarrierError if run alone
        return real(self, block_ids, result, rec)
    monkeypatch.setattr(cls, "store_group", store_group)
    cfg = EngineConfig(local_bits=4, inner_size=2, b_r=1e-3,
                       codec_backend=backend, pipeline_depth=depth)
    with Simulator(build_circuit("qft", 10), cfg) as sim:
        if batched:
            states = [np.asarray(lane.statevector())
                      for lane in sim.run_batch([None] * 2)]
        else:
            states = [np.asarray(sim.run().statevector())]
        store = sim._engine.store
        blobs = {k: store.get(k) for k in store.keys()}
        recs = [r for r in spans.recent(sim.stats.last_run) if r.stage >= 0]
    monkeypatch.undo()
    return states, blobs, recs, sizes


@pytest.mark.parametrize("batched", [False, True])
@pytest.mark.parametrize("backend", ["host", "device"])
def test_per_block_stores_run_at_once_and_store_the_same_bytes(
        monkeypatch, backend, batched):
    fanned = _fan_out_run(monkeypatch, backend, batched, depth=2,
                          fetch_workers=2, rendezvous=True)
    depth1 = _fan_out_run(monkeypatch, backend, batched, depth=1,
                          fetch_workers=2)
    states, blobs, recs, sizes = fanned
    assert recs and all(r.waves > 1 for r in recs)
    assert max(r.store_peak_inflight for r in recs) > 1
    assert all(r.store_peak_inflight == 1 for r in depth1[2])
    # one block a store call in the pool; a group a call on the caller's
    # thread
    assert sizes == {1} and depth1[3] == {4}
    # the same bytes under every key, and the same decoded state
    assert blobs.keys() == depth1[1].keys()
    for k in blobs:
        assert blobs[k] == depth1[1][k], f"block {k} differs"
    for a, b in zip(states, depth1[0], strict=True):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("point", ["pipeline.store", "codec.encode"])
@pytest.mark.parametrize("backend", ["host", "device"])
def test_failed_block_store_names_its_key(backend, point):
    """A fault in one block's store task raises the typed error, naming
    that block's key: its blob is the only one the stage left as it was,
    because the barrier lets every other block's store finish first."""
    jax = pytest.importorskip("jax")
    from repro.compression.pwrel import PwRelParams
    from repro.compression.store import BlockStore
    from repro.core.faults import inject_faults
    from repro.core.pipeline import make_backend
    from repro.errors import StoreIOError

    bsz, n_groups, n_blocks = 64, 4, 2
    back = make_backend(backend, BlockStore(), PwRelParams(1e-3), bsz)
    rng = np.random.default_rng(5)
    for k in range(n_groups * n_blocks):
        back.encode_host_block(k, (rng.standard_normal(bsz) + 1j
                                   * rng.standard_normal(bsz))
                               .astype(np.complex64))
    before = {k: back.store.get(k) for k in back.store.keys()}
    block_ids = np.arange(n_groups * n_blocks).reshape(n_groups, n_blocks)
    pipe = StagePipeline(back, depth=2, fetch_workers=2)
    with inject_faults([f"{point}:ioerror:hit=3"]) as inj:
        with pytest.raises(StoreIOError) as err:
            with pipe:
                pipe.run_stage(block_ids, fn=None, mats=[],
                               wave_fn=jax.jit(lambda p: p * 2.0))
    assert inj.fired[f"{point}:ioerror"] == 1
    assert pipe._dec_pool is None and pipe._com_pool is None
    failed = err.value.key
    assert f"pipeline store failed key={failed}" in str(err.value)
    unchanged = {k for k in before if back.store.get(k) == before[k]}
    assert unchanged == {failed}


def test_store_fan_out_stress_keeps_every_block(monkeypatch):
    """Many small block stores on a store pool wider than the host, with
    a very short thread switch interval: every block is stored with the
    same bytes as the one-wave-at-a-time loop, and the in-flight gauge
    returns to zero within the pool's width."""
    import sys

    import jax

    from repro.compression.pwrel import PwRelParams
    from repro.compression.store import BlockStore
    from repro.core import pipeline as pipeline_mod

    bsz, n_groups, n_blocks = 32, 32, 4
    monkeypatch.setattr(pipeline_mod.os, "cpu_count", lambda: 16)
    rng = np.random.default_rng(11)
    amps = (rng.standard_normal((n_groups * n_blocks, bsz))
            + 1j * rng.standard_normal((n_groups * n_blocks, bsz))) \
        .astype(np.complex64)
    block_ids = np.arange(n_groups * n_blocks).reshape(n_groups, n_blocks)
    wave_fn = jax.jit(lambda p: p * 0.5)

    def stage(fetch_workers):
        back = HostCodecBackend(BlockStore(), PwRelParams(1e-3), bsz)
        for k, a in enumerate(amps):
            back.encode_host_block(k, a)
        with StagePipeline(back, depth=4, fetch_workers=fetch_workers) as p:
            rec = p.run_stage(block_ids, fn=None, mats=[], wave_fn=wave_fn)
            width = p._com_pool._max_workers if p._com_pool else 1
            with p._stores_lock:
                running = p._stores_running
        return back, rec, width, running

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        fanned, rec, width, running = stage(fetch_workers=2)
    finally:
        sys.setswitchinterval(old)
    serial, ref_rec, _, _ = stage(fetch_workers=0)
    assert width == 13 and running == 0
    assert 1 <= rec.store_peak_inflight <= width
    assert ref_rec.store_peak_inflight == 1
    assert rec.blocks_stored == ref_rec.blocks_stored == n_groups * n_blocks
    for k in range(n_groups * n_blocks):
        assert fanned.store.get(k) == serial.store.get(k), f"block {k}"
