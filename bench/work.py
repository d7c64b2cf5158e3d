"""Work the algorithm needs, counted from shapes, and the roofline over it.

Counted per execution, not read from the program's clocks:

* a **stage program** updates one SV group of ``2^nv`` amplitudes
  (``nv = local_bits + m``), one lane per row.  It must read and write
  the group's two float32 planes once: ``16 * 2^nv`` bytes.  Each fused
  k-qubit unitary costs ``8 * 2^k`` flops per amplitude (a complex
  multiply-add per input amplitude), a diagonal one ``6`` (one complex
  multiply).  How the program implements a unitary (a padded
  ``kron(I, U, I)`` operator, a chain of selects) does not change the
  count.
* a **codec call** turns a block of ``2^b`` amplitudes from float32
  planes into the compressed wire or back: it reads one and writes the
  other, ``8 * 2^b + wire_bytes(2^b)`` bytes.  Its arithmetic is a few
  elementwise operations per amplitude and never binds on a TPU, so
  only bytes are counted.

The least time of a piece of work is the larger of its flops over the
chip's peak rate and its bytes over the chip's memory bandwidth
(:func:`least_seconds`); a roofline share is that time over the device
time the trace shows.
"""
from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path

__all__ = ["Peaks", "peaks_for", "Work", "JobWork", "wire_bytes",
           "stage_work", "codec_work", "job_work", "least_seconds"]

_PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


@dataclass(frozen=True)
class Peaks:
    kind: str
    flops_per_s: float
    hbm_bytes_per_s: float
    hbm_bytes: float
    source: str


def peaks_for(device_kind: str) -> Peaks:
    """The peaks of ``device_kind``; an unknown kind is an error."""
    table = json.loads(_PEAKS_FILE.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    e = table[device_kind]
    return Peaks(device_kind, float(e["flops_per_s"]),
                 float(e["hbm_bytes_per_s"]), float(e["hbm_bytes"]),
                 e["source"])


@dataclass(frozen=True)
class Work:
    """Flops and bytes of some executions, and their least time summed
    execution by execution (so a mix of bounds adds up correctly)."""

    flops: float = 0.0
    bytes: float = 0.0
    least_s: float = 0.0
    #: seconds of least time that the flops bound, and that bytes bound
    flops_bound_s: float = 0.0
    bytes_bound_s: float = 0.0

    def __add__(self, other: "Work") -> "Work":
        return Work(self.flops + other.flops, self.bytes + other.bytes,
                    self.least_s + other.least_s,
                    self.flops_bound_s + other.flops_bound_s,
                    self.bytes_bound_s + other.bytes_bound_s)

    def times(self, k: int) -> "Work":
        return Work(self.flops * k, self.bytes * k, self.least_s * k,
                    self.flops_bound_s * k, self.bytes_bound_s * k)

    @property
    def bound(self) -> str:
        """Which bound holds most of the least time."""
        return "flops" if self.flops_bound_s > self.bytes_bound_s \
            else "bytes"


def least_seconds(flops: float, nbytes: float, peaks: Peaks) -> Work:
    """One execution's work and least time on ``peaks``."""
    tf = flops / peaks.flops_per_s
    tb = nbytes / peaks.hbm_bytes_per_s
    least = max(tf, tb)
    return Work(flops, nbytes, least,
                least if tf > tb else 0.0, least if tf <= tb else 0.0)


def wire_bytes(n: int) -> int:
    """Compressed wire of one n-amplitude block: per plane, 16-bit codes
    in whole int32 words, one sign bit per element in whole int32 words,
    and one float32 anchor."""
    code_words = (n + 1) // 2
    sign_words = (n + 31) // 32
    return 2 * 4 * (code_words + sign_words + 1)


def stage_work(fused, nv: int, peaks: Peaks) -> Work:
    """One stage program on one group row: ``fused`` is the stage's
    ``((vqubits, is_diagonal), ...)`` plan."""
    amps = float(1 << nv)
    flops = sum((6.0 if diag else 8.0 * (1 << len(vq))) * amps
                for vq, diag in fused)
    return least_seconds(flops, 16.0 * amps, peaks)


def codec_work(block_amps: int, peaks: Peaks) -> Work:
    """One block through one codec direction (encode or decode)."""
    return least_seconds(0.0, 8.0 * block_amps + wire_bytes(block_amps),
                         peaks)


@dataclass(frozen=True)
class JobWork:
    """One job's device work: stage programs and codec calls."""

    gates: Work
    codec: Work
    stages: int
    stage_rows: int
    codec_blocks: int


def job_work(stages, local_bits: int, lanes: int, peaks: Peaks) -> JobWork:
    """Work of one whole job: ``stages`` yields ``(fused, m, n_groups)``
    per non-empty stage of the plan, every group of every lane runs the
    stage program once, and every block is decoded once and encoded
    once."""
    gates, codec = Work(), Work()
    n_stages = rows = blocks = 0
    one_block = codec_work(1 << local_bits, peaks)
    for fused, m, n_groups in stages:
        if not fused:
            continue
        n_stages += 1
        rows += n_groups * lanes
        gates += stage_work(fused, local_bits + m, peaks).times(
            n_groups * lanes)
        nblk = n_groups * (1 << m) * lanes
        blocks += nblk
        codec += one_block.times(2 * nblk)
    return JobWork(gates, codec, n_stages, rows, 2 * blocks)
