"""Reduce a profiler trace of the measured window to per-layer numbers.

:func:`load` reads the ``.xplane.pb`` that ``jax.profiler`` writes into a
:class:`Trace`: the device's module and op executions (from each TPU
plane's ``XLA Modules`` and ``XLA Ops`` lines) and the host's spans.
:func:`reduce` is pure arithmetic on a :class:`Trace`, so it is checked
on a small recorded trace (``tests/data/trace_small.json``):

* busy seconds: the union of the device's op intervals inside the
  window, averaged over the devices;
* per-layer device seconds: the summed durations of the module
  executions whose name matches the layer's pattern;
* the longest idle gaps between busy intervals, each named by what
  each host thread was doing at the middle of the gap (its innermost
  span that is not a wait);
* the device modules that took the most time.
"""
from __future__ import annotations

import json
import re
from dataclasses import dataclass, field
from pathlib import Path

__all__ = ["Span", "Trace", "load", "reduce", "find_xplane", "from_json",
           "LAYER_PATTERNS"]

#: device modules of each layer, by the names the programs have today:
#: the jitted stage programs are ``fn`` closures (``jit_fn``), the codec
#: programs ``_encode_group_jit`` and ``_decode_blocks_jit``
LAYER_PATTERNS = {
    "gates": r"^jit_fn(\(|$|\.)",
    "codec": r"^jit__(encode_group|decode_blocks)_jit(\(|$|\.)",
}


@dataclass(frozen=True)
class Span:
    name: str
    start_ns: int
    end_ns: int
    #: device id for device events, thread line name for host spans
    where: str = ""

    @property
    def dur_ns(self) -> int:
        return self.end_ns - self.start_ns


@dataclass
class Trace:
    modules: list = field(default_factory=list)      # device module runs
    ops: list = field(default_factory=list)          # device op runs
    host: list = field(default_factory=list)         # host spans


def find_xplane(log_dir: str | Path) -> Path:
    """The one ``.xplane.pb`` under a ``jax.profiler`` log directory."""
    found = sorted(Path(log_dir).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def _is_device_plane(name: str) -> bool:
    return re.match(r"^/device:TPU:\d+$", name) is not None


def load(path: str | Path, min_host_ns: int = 1_000_000) -> Trace:
    """Read an ``.xplane.pb``: every device module and op execution, and
    the host spans of the threads that run Python (the profiler's
    ``$file:line function`` events and the benchmark's annotations) that
    last ``min_host_ns`` or longer."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(path))
    tr = Trace()
    for plane in data.planes:
        if _is_device_plane(plane.name):
            dev = plane.name.rsplit(":", 1)[-1]
            for line in plane.lines:
                if line.name == "XLA Modules":
                    dst = tr.modules
                elif line.name == "XLA Ops":
                    dst = tr.ops
                else:
                    continue
                for e in line.events:
                    s = int(e.start_ns)
                    dst.append(Span(e.name, s, s + int(e.duration_ns), dev))
        elif plane.name.startswith("/host:CPU"):
            # thread lines need not have unique names: number them
            for i, line in enumerate(plane.lines):
                where = f"{line.name or 'thread'}#{i}"
                kept = [Span(e.name, int(e.start_ns),
                             int(e.start_ns) + int(e.duration_ns), where)
                        for e in line.events
                        if int(e.duration_ns) >= min_host_ns]
                if any(sp.name.startswith(("$", "bench.")) for sp in kept):
                    tr.host.extend(kept)
    return tr


def _clip(spans, t0, t1):
    out = []
    for s in spans:
        a, b = max(s.start_ns, t0), min(s.end_ns, t1)
        if b > a:
            out.append(Span(s.name, a, b, s.where))
    return out


def _union(spans) -> list[tuple[int, int]]:
    merged: list[list[int]] = []
    for s in sorted(spans, key=lambda s: s.start_ns):
        if merged and s.start_ns <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], s.end_ns)
        else:
            merged.append([s.start_ns, s.end_ns])
    return [(a, b) for a, b in merged]


#: host spans that only wait for another thread's work
_WAITS = ("acquire", "wait", "result", "get", "sleep", "join", "select",
          "_worker")


def _doing(host, t: int) -> str:
    """What the host's threads were doing at time ``t``: for each
    thread, the innermost span covering ``t`` that is not a wait."""
    best: dict[str, Span] = {}
    for s in host:
        if not s.start_ns <= t < s.end_ns or s.name.startswith("bench."):
            continue
        if s.name.rsplit(" ", 1)[-1] in _WAITS:
            continue
        b = best.get(s.where)
        if b is None or s.dur_ns < b.dur_ns:
            best[s.where] = s
    if not best:
        return "(no host span)"
    return " | ".join(f"{w}: {s.name}" for w, s in sorted(best.items()))


def reduce(tr: Trace, t0: int, t1: int, patterns=None, top: int = 10) -> dict:
    """Numbers of the window ``[t0, t1)`` (nanoseconds, trace clock).

    Returns ``window_s``, ``busy_s`` (mean over devices), ``idle_share``
    (0..1), ``layer_s`` (device seconds per layer pattern, summed over
    devices), ``device_ops`` (top modules by device seconds) and
    ``idle_gaps`` (longest gaps, named by the host span they fall in).
    """
    patterns = LAYER_PATTERNS if patterns is None else patterns
    window_s = (t1 - t0) / 1e9
    ops = _clip(tr.ops, t0, t1)
    modules = _clip(tr.modules, t0, t1)
    devices = sorted({s.where for s in ops} | {s.where for s in modules})
    busy_ns, gaps = 0, []
    for dev in devices:
        # an op runs inside its module: the union of both is the time
        # the device had work
        u = _union([s for s in ops + modules if s.where == dev])
        busy_ns += sum(b - a for a, b in u)
        edges = [t0] + [x for ab in u for x in ab] + [t1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps.append((b - a, (a + b) // 2))
    n_dev = max(1, len(devices))
    busy_s = busy_ns / n_dev / 1e9
    layer_s = {k: 0.0 for k in patterns}
    per_module: dict[str, float] = {}
    for s in modules:
        per_module[s.name] = per_module.get(s.name, 0.0) + s.dur_ns / 1e9
        for layer, pat in patterns.items():
            if re.search(pat, s.name):
                layer_s[layer] += s.dur_ns / 1e9
    host = [s for s in tr.host if s.end_ns > t0 and s.start_ns < t1]
    gaps.sort(reverse=True)
    return {
        "window_s": window_s,
        "busy_s": busy_s,
        "idle_share": 1.0 - busy_s / window_s if window_s > 0 else None,
        "layer_s": layer_s,
        "n_devices": len(devices),
        "device_ops": sorted(per_module.items(),
                             key=lambda kv: -kv[1])[:top],
        "idle_gaps": [[_doing(host, mid), ns / 1e9]
                      for ns, mid in gaps[:top]],
    }


def from_json(d: dict) -> Trace:
    """A :class:`Trace` from plain lists of ``[name, start_ns, dur_ns,
    where]`` under ``modules``, ``ops`` and ``host`` (a recorded trace)."""
    def spans(rows):
        return [Span(n, s, s + d, w) for n, s, d, w in rows]
    return Trace(spans(d["modules"]), spans(d["ops"]), spans(d["host"]))
