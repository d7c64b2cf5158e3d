"""The trace reduction on a hand-made trace and on a small recorded one."""
import json
from pathlib import Path

import numpy as np
import pytest

import trace_reduce as T

DATA = Path(__file__).resolve().parent / "data"


def _spans(rows):
    return [T.Span(n, s, s + d, w) for n, s, d, w in rows]


def test_hand_made_trace():
    # window 0..100 ns; modules [10,30) gates, [40,50) codec, [45,60) codec
    # (overlapping: busy is their union), an op [70,75) outside any module
    tr = T.Trace(
        modules=_spans([["jit_fn(1)", 10, 20, "0"],
                        ["jit__decode_blocks_jit(2)", 40, 10, "0"],
                        ["jit__encode_group_jit(3)", 45, 15, "0"]]),
        ops=_spans([["op", 12, 5, "0"], ["op", 70, 5, "0"],
                    ["op", 95, 10, "0"]]),
        host=_spans([["$a.py:1 outer", 0, 100, "main#0"],
                     ["$a.py:2 inner", 60, 10, "main#0"],
                     ["$b.py:3 acquire", 0, 100, "worker#1"],
                     ["$zlib compress", 75, 20, "worker#2"]]))
    r = T.reduce(tr, 0, 100)
    assert r["window_s"] == pytest.approx(100e-9)
    # busy: [10,30) + [40,60) + [70,75) + [95,100) = 20 + 20 + 5 + 5
    assert r["busy_s"] == pytest.approx(50e-9)
    assert r["idle_share"] == pytest.approx(0.5)
    assert r["layer_s"]["gates"] == pytest.approx(20e-9)
    assert r["layer_s"]["codec"] == pytest.approx(25e-9)
    assert r["device_ops"][0][0] == "jit_fn(1)"
    # gaps: [0,10) [30,40) [60,70) [75,95) -> the longest is [75,95)
    gaps = r["idle_gaps"]
    assert [g[1] for g in gaps] == pytest.approx([20e-9, 10e-9, 10e-9,
                                                 10e-9])
    # mid 85: main's innermost non-wait span is outer; worker#1 waits;
    # worker#2 compresses
    assert gaps[0][0] == "main#0: $a.py:1 outer | worker#2: $zlib compress"
    # mid 65 falls in main's inner span
    assert any(g[0] == "main#0: $a.py:2 inner" for g in gaps)


def _timeline_busy(spans, t0, t1):
    """Busy ns by a boolean timeline at 1 ns resolution (independent of
    the interval merge the reduction does)."""
    busy = np.zeros(t1 - t0, bool)
    for s in spans:
        a, b = max(s.start_ns, t0), min(s.end_ns, t1)
        if b > a:
            busy[a - t0:b - t0] = True
    return int(busy.sum()), busy


def test_recorded_trace():
    fx = json.loads((DATA / "trace_small.json").read_text())
    tr = T.from_json(fx)
    t0, t1 = fx["window"]
    r = T.reduce(tr, t0, t1)
    busy_ns, busy = _timeline_busy(tr.modules + tr.ops, t0, t1)
    assert r["busy_s"] == pytest.approx(busy_ns / 1e9, abs=1e-12)
    assert r["idle_share"] == pytest.approx(1 - busy_ns / (t1 - t0))
    # per layer: the modules' own durations, summed by name
    sums = {"gates": 0.0, "codec": 0.0}
    for m in tr.modules:
        d = (min(m.end_ns, t1) - max(m.start_ns, t0)) / 1e9
        if m.name.startswith("jit_fn("):
            sums["gates"] += d
        elif m.name.startswith(("jit__decode_blocks_jit(",
                                "jit__encode_group_jit(")):
            sums["codec"] += d
    assert r["layer_s"] == pytest.approx(sums)
    assert sums["gates"] > 0 and sums["codec"] > 0
    # the longest gap is the longest run of idle timeline
    edges = np.flatnonzero(np.diff(np.concatenate([[1], busy, [1]])))
    runs = edges[1::2] - edges[0::2]
    assert r["idle_gaps"][0][1] == pytest.approx(runs.max() / 1e9)
    assert sum(g[1] for g in r["idle_gaps"]) <= r["window_s"] - r["busy_s"]
    ops = [t for _, t in r["device_ops"]]
    assert ops == sorted(ops, reverse=True)
