"""Device-resident lossy codec (paper §4.3 on the accelerator).

The paper's headline design point: the lossy half of the compressor runs
*next to the compute*, so only the compressed representation crosses the
host↔device boundary.  Per real plane of an n-amplitude block, the wire
format is exact-sized:

    codes       (n,)              uint16  — quantizer output, packed into
                                            u16-pair words by
                                            ``kernels.pack.pack_codes_tiles``
    sign_bytes  (4*ceil(n/32),)   uint8   — ballot-packed sign bits
                                            (LSB-first, fused into
                                            ``quantize_tiles``)
    l_max       (1, 1)            float32 — quantizer anchor scalar

i.e. ~2.13 bytes per element instead of 4 (f32) — ~4.25 vs 8 bytes per
complex amplitude — before the host lossless stage shrinks it further.
On the device both streams stay the kernels' int32 words, cut to the
ceil(n/2) and ceil(n/32) words that hold n elements; the host views the
fetched words as uint16 / uint8 (a little-endian view, no copy) and
builds the words for a decode the same way.  No 16- or 8-bit array ever
exists on the device: a TPU lays out such narrow arrays padded many
times over.

Encode path (device -> store):   ``encode_group_device`` dispatches the
quantize + pack kernels per block, ``wire_to_segments`` runs the host
lossless stage on the fetched wire arrays.

Decode path (store -> device):   ``segments_to_wire`` inflates a block's
segments back to wire arrays, ``decode_block_device`` ships them to the
accelerator and runs unpack + dequantize there.

Planes are zero-padded on device to a multiple of 128 lanes around the
kernels; pad elements quantize to the exact-zero escape code and never
cross the boundary or reach the store — pwrel-format blocks written by one
backend are bit-identical to the other's, so the two are freely
interchangeable.  (RAW-escape blocks are the one exception: the device
path never ships raw amplitudes, so its RAW fallback stores the lossy
reconstruction — same size bound, same error bound, different bytes.)
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..faults import fault_point
from ..kernels import pack as _pk
from ..kernels import quantize as _qz
from .lossless import decode_bitmap, decode_codes, encode_bitmap, encode_codes
from .pwrel import CODE_MAX, PwRelParams, log_step
from .segments import BlockSegments, PlaneSegments

__all__ = [
    "PlaneWire", "plane_geometry", "sign_wire_bytes",
    "encode_group_device", "encode_group_planes", "fetch_group_wire",
    "wire_to_segments", "segments_to_wire", "decode_block_device",
    "decode_blocks_device", "decode_blocks_planes",
]

_LANES = 128


class PlaneWire(NamedTuple):
    """One plane's boundary-crossing representation: int32 words on the
    device, their uint16 / uint8 views on the host.  For an odd n the
    code view holds one zero pad code; readers take the first n."""

    codes: jax.Array | np.ndarray        # host: (2*ceil(n/2),) u16
    sign_bytes: jax.Array | np.ndarray   # host: (4*ceil(n/32),) u8, LSB-first
    l_max: jax.Array | np.ndarray        # (1, 1) f32

    @property
    def nbytes(self) -> int:
        return int(self.codes.nbytes + self.sign_bytes.nbytes
                   + self.l_max.nbytes)


def plane_geometry(n: int) -> tuple[int, int]:
    """(rows, pad) for an n-element plane padded to 128-lane rows."""
    pad = (-n) % _LANES
    return (n + pad) // _LANES, pad


def sign_wire_bytes(n: int) -> int:
    """Sign-bitmap wire size: whole ballot words, 4 bytes per 32 elements."""
    return 4 * _sign_words(n)


def _code_words(n: int) -> int:
    return (n + 1) // 2


def _sign_words(n: int) -> int:
    return (n + 31) // 32


# --------------------------------------------------------------------------
# encode: device kernels -> wire -> host lossless stage
# --------------------------------------------------------------------------

def _encode_plane_dev(x: jax.Array, pad: int, step: float) -> PlaneWire:
    n = x.shape[0]
    if pad:
        x = jnp.concatenate([x, jnp.zeros((pad,), jnp.float32)])
    x2d = x.reshape(-1, _LANES)
    max_abs = jnp.max(jnp.abs(x2d))
    l_max = jnp.where(max_abs > 0,
                      jnp.log2(jnp.maximum(max_abs, 1e-45)), 0.0)
    l_max = l_max.reshape(1, 1).astype(jnp.float32)
    codes, packed_signs, _flags = _qz.quantize_tiles(x2d, l_max, step)
    return PlaneWire(
        _pk.pack_codes_tiles(codes).reshape(-1)[:_code_words(n)],
        packed_signs.reshape(-1)[:_sign_words(n)], l_max)


@partial(jax.jit, static_argnames=("n_blocks", "step"))
def _encode_group_jit(planes: jax.Array, n_blocks: int, step: float):
    bsz = planes.shape[1] // n_blocks
    _, pad = plane_geometry(bsz)
    with jax.named_scope("codec.encode"):
        re = planes[0].reshape(n_blocks, bsz)
        im = planes[1].reshape(n_blocks, bsz)
        out = []
        for i in range(n_blocks):
            out.append((
                _encode_plane_dev(re[i], pad, step),
                _encode_plane_dev(im[i], pad, step),
            ))
        return tuple(out)


def encode_group_planes(planes: jax.Array, n_blocks: int,
                        params: PwRelParams):
    """Dispatch the lossy encode of a planes-resident group on its device.

    Args:
        planes: (2, n_blocks * 2^b) f32 re/im plane stack (device-resident)
            — the stage compute's native representation; no complex64 is
            materialized on the encode path.
        n_blocks: SV blocks in the group (2^m).
        params: pwrel bound.

    Returns:
        Tuple of ``(re: PlaneWire, im: PlaneWire)`` per block — device
        arrays, dispatched asynchronously (nothing is fetched yet).
    """
    return _encode_group_jit(jnp.asarray(planes, jnp.float32), n_blocks,
                             log_step(params.b_r))


def encode_group_device(amps: jax.Array, n_blocks: int, params: PwRelParams):
    """Complex-array convenience over :func:`encode_group_planes` —
    identical stored bytes (a complex64's components are already f32)."""
    fault_point("codec.encode")
    planes = jnp.stack([jnp.real(amps), jnp.imag(amps)]).astype(jnp.float32)
    return encode_group_planes(planes, n_blocks, params)


def fetch_group_wire(encoded) -> tuple[list[tuple[PlaneWire, PlaneWire]], int]:
    """Block on the device encode and fetch wire arrays to host numpy.

    Returns (per-block host PlaneWire pairs, total bytes moved d2h).
    """
    out, moved = [], 0
    for re_w, im_w in encoded:
        host_pair = []
        for w in (re_w, im_w):
            h = PlaneWire(np.asarray(w.codes).view("<u2").reshape(-1),
                          np.asarray(w.sign_bytes).view(np.uint8).reshape(-1),
                          np.asarray(w.l_max))
            moved += h.nbytes
            host_pair.append(h)
        out.append(tuple(host_pair))
    return out, moved


def _wire_plane_to_segments(w: PlaneWire, n: int,
                            prescan: bool) -> PlaneSegments:
    u16 = np.asarray(w.codes, dtype="<u2")[:n]
    bits = np.unpackbits(np.asarray(w.sign_bytes, dtype=np.uint8),
                         bitorder="little", count=n).astype(bool)
    return PlaneSegments(l_max=float(np.asarray(w.l_max).reshape(())),
                         codes=encode_codes(u16),
                         bitmap=encode_bitmap(bits, prescan))


def _wire_plane_to_f32(w: PlaneWire, n: int, step: float) -> np.ndarray:
    """Pure-numpy dequantize of a host wire plane (pwrel.py math, GIL-free)."""
    codes = np.asarray(w.codes, dtype="<u2")[:n]
    bits = np.unpackbits(np.asarray(w.sign_bytes, dtype=np.uint8),
                         bitorder="little", count=n).astype(bool)
    d = np.float32(CODE_MAX) - codes.astype(np.float32)
    mag = np.exp2(np.float32(np.asarray(w.l_max).reshape(()))
                  - d * np.float32(step)).astype(np.float32)
    mag[codes == 0] = 0.0
    return np.where(bits, -mag, mag).astype(np.float32)


def wire_to_segments(pair: tuple[PlaneWire, PlaneWire], n: int,
                     prescan: bool = True,
                     params: PwRelParams | None = None) -> BlockSegments:
    """Host lossless stage: fetched wire arrays -> structured block segments.

    When ``params`` is given, the host codec's never-inflate contract is
    honored: if the pwrel segments would exceed the raw block, the wire is
    dequantized on the host (pure numpy — the quantized data is all the
    device shipped, so the RAW bytes hold the reconstruction, not the
    pre-quantization amplitudes the host encoder would have stored).
    """
    seg = BlockSegments(n_amps=n, prescan=prescan,
                        re=_wire_plane_to_segments(pair[0], n, prescan),
                        im=_wire_plane_to_segments(pair[1], n, prescan))
    if params is not None and seg.nbytes >= seg.raw_nbytes + 8:
        step = log_step(params.b_r)
        amps = (_wire_plane_to_f32(pair[0], n, step)
                + 1j * _wire_plane_to_f32(pair[1], n, step)) \
            .astype(np.complex64)
        seg = BlockSegments(n_amps=n, raw=amps.tobytes())
    return seg


# --------------------------------------------------------------------------
# decode: host lossless stage -> wire -> device kernels
# --------------------------------------------------------------------------

def _segments_plane_to_wire(p: PlaneSegments, n: int,
                            prescan: bool) -> PlaneWire:
    u16 = np.asarray(decode_codes(p.codes, n))
    bits = decode_bitmap(p.bitmap, n, prescan)
    sign_bytes = np.packbits(bits, bitorder="little")
    want = sign_wire_bytes(n)
    if sign_bytes.size < want:
        sign_bytes = np.concatenate(
            [sign_bytes, np.zeros(want - sign_bytes.size, np.uint8)])
    l_max = np.asarray(p.l_max, dtype=np.float32).reshape(1, 1)
    return PlaneWire(u16, sign_bytes, l_max)


def segments_to_wire(seg: BlockSegments) -> tuple[PlaneWire, PlaneWire]:
    """Inflate a block's lossless segments to host wire arrays (GIL-free)."""
    assert not seg.is_raw, "RAW blocks bypass the device codec"
    return (_segments_plane_to_wire(seg.re, seg.n_amps, seg.prescan),
            _segments_plane_to_wire(seg.im, seg.n_amps, seg.prescan))


def _host_words(a: np.ndarray, count: int) -> np.ndarray:
    """A host wire stream as ``count`` int32 words (zero-padded bytes)."""
    raw = np.ascontiguousarray(a).view(np.uint8).reshape(-1)
    words = np.zeros(count * 4, np.uint8)
    words[:raw.size] = raw
    return words.view("<i4")


def _word_rows(words: jax.Array, rows: int, width: int) -> jax.Array:
    """Wire words -> the (rows, width) tile the unpack kernels read."""
    pad = rows * width - words.shape[0]
    if pad:
        words = jnp.concatenate([words, jnp.zeros((pad,), jnp.int32)])
    return words.reshape(rows, width)


@partial(jax.jit, static_argnames=("n", "step"))
def _decode_blocks_jit(codes, signs, l_max, n: int, step: float):
    """codes (2k, ceil(n/2)) / signs (2k, ceil(n/32)) i32 words / l_max
    (2k, 1, 1) f32, planes in block order [re0, im0, re1, im1, ...] ->
    (k, 2, n) f32."""
    k2 = codes.shape[0]
    rows, _ = plane_geometry(n)
    with jax.named_scope("codec.decode"):
        planes = [
            _qz.dequantize_tiles(
                _pk.unpack_codes_tiles(
                    _word_rows(codes[i], rows, _LANES // 2)),
                _word_rows(signs[i], rows, _LANES // 32),
                l_max[i], step).reshape(-1)[:n]
            for i in range(k2)]
        return jnp.stack(planes).reshape(k2 // 2, 2, n)


@partial(jax.jit, static_argnames=())
def _planes_to_complex(planes: jax.Array) -> jax.Array:
    """(..., 2, n) f32 plane pairs -> (..., n) complex64."""
    return (planes[..., 0, :] + 1j * planes[..., 1, :]).astype(jnp.complex64)


def decode_blocks_planes(pairs: list, n: int, params: PwRelParams,
                         device) -> tuple[jax.Array, int]:
    """Ship several blocks' wire arrays to ``device`` in three batched
    transfers and decode them in one kernel dispatch.

    Args:
        pairs: per-block ``(re, im)`` host :class:`PlaneWire` tuples.

    Returns (device f32 planes (len(pairs), 2, n), bytes moved h2d) — the
    stage compute's native representation; no complex64 is materialized.
    The decode is dispatched asynchronously — callers can overlap it with
    compute of the previous group (§4.2).
    """
    planes = [w for pair in pairs for w in pair]
    codes = np.stack([_host_words(w.codes, _code_words(n)) for w in planes])
    signs = np.stack([_host_words(w.sign_bytes, _sign_words(n))
                      for w in planes])
    l_max = np.stack([np.asarray(w.l_max) for w in planes])
    moved = codes.nbytes + signs.nbytes + l_max.nbytes
    blocks = _decode_blocks_jit(
        jax.device_put(codes, device), jax.device_put(signs, device),
        jax.device_put(l_max, device), n=n, step=log_step(params.b_r))
    return blocks, moved


def decode_blocks_device(pairs: list, n: int, params: PwRelParams,
                         device) -> tuple[jax.Array, int]:
    """Complex-array convenience over :func:`decode_blocks_planes`.

    Returns (device complex64 blocks (len(pairs), n), bytes moved h2d).
    """
    fault_point("codec.decode")
    planes, moved = decode_blocks_planes(pairs, n, params, device)
    return _planes_to_complex(planes), moved


def decode_block_device(pair: tuple[PlaneWire, PlaneWire], n: int,
                        params: PwRelParams, device) -> tuple[jax.Array, int]:
    """Single-block convenience over :func:`decode_blocks_device`."""
    blocks, moved = decode_blocks_device([pair], n, params, device)
    return blocks[0], moved
