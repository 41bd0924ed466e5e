"""Gear CDC rolling-hash Pallas kernel.

The sequential gear recurrence is a linear recurrence, so the hash is a
32-tap windowed weighted sum (DESIGN.md S3):

    h[t] = sum_{j=0..31} 2^j * gear[byte[t-j]]   (mod 2^32)

The stream is laid out (rows, 128) -- byte t at row t // 128, lane
t % 128 -- and each grid cell computes the hashes of ROWS rows (TILE
bytes).  Pallas BlockSpecs cannot express halos directly, so the kernel
receives the data *twice* with shifted index maps -- the current tile and
the block of rows before it -- and takes its history from that block's
tail (zeroed for the first tile, matching the reference's implicit
zero-history).

The gear-table lookup is a lane gather: the 256-entry table is held as
two 128-lane rows and each byte picks its lane from the row its top bit
selects (Mosaic lowers this 2-D, same-shape gather, not a 1-D
``jnp.take``).  The window sum is built by doubling (a 2-tap sum, then
4, ..., 32) with lane and sublane rotations, so no slice is unaligned.
All of it runs in VMEM: HBM traffic is the tile and its history block
read (TILE + HALO_BLOCK * 128 bytes per cell) plus the output.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.chunking import GEAR_TABLE, WINDOW
from repro.kernels.launches import TRACES

TILE = 8192  # output bytes per grid cell
LANES = 128  # stream bytes per row
ROWS = TILE // LANES  # rows per grid cell
HALO_BLOCK = 32  # rows of the history block (the uint8 sublane tile)
HALO_ROWS = 8  # of which the kernel looks up the last (>= 31 bytes)
# bit-identical int32 reinterpret, split into the table's two lane rows
_GEAR_ROWS = GEAR_TABLE.view(np.int32).reshape(2, LANES)


@functools.lru_cache(maxsize=1)
def _device_gear_table() -> jnp.ndarray:
    """Device-resident (2, 128) gear table, uploaded once per process."""
    return jnp.asarray(_GEAR_ROWS)


def bucket_len(n: int) -> int:
    """Padded stream length for ``n`` bytes: a power-of-two multiple of TILE.

    ``_gear_hash_padded`` compiles once per distinct padded length, so an
    ingest path hashing arbitrary-size windows must quantize lengths or it
    retraces on every new size.  Power-of-two tile counts bound the set of
    compiled shapes to log2(N/TILE) while wasting at most 2x compute.
    """
    tiles = max(1, -(-n // TILE))
    return TILE * (1 << (tiles - 1).bit_length())


def pad_to_bucket(data):
    """Zero-pad a (N,) uint8 array (np or jnp) to ``bucket_len(N)``.

    The single place that applies the bucketing contract -- every gear
    entry point (Pallas wrapper and the jitted ref oracles in ``ops``)
    pads through here so the compiled-shape set stays in lockstep.
    """
    n = data.shape[0]
    pad = bucket_len(n) - n
    if pad:
        xp = jnp if isinstance(data, jnp.ndarray) else np
        return xp.pad(data, (0, pad))
    return data


def _kernel(cur_ref, prev_ref, gear_ref, out_ref):
    out_ref[...] = _hash_tile(pl.program_id(0), cur_ref, prev_ref, gear_ref)


def _lookup(gear, b):
    """gear[b] for (rows, LANES) int32 bytes b, as uint32.

    Each byte's low 7 bits index a lane of the (2, LANES) table; its top
    bit chooses the row.  Both gathers are ``take_along_axis`` on equal
    (rows, LANES) shapes, the form Mosaic lowers to a lane gather.
    """
    rows = b.shape[0]
    lane = b & (LANES - 1)
    lo = jnp.take_along_axis(jnp.broadcast_to(gear[0:1], (rows, LANES)),
                             lane, axis=1)
    hi = jnp.take_along_axis(jnp.broadcast_to(gear[1:2], (rows, LANES)),
                             lane, axis=1)
    return jnp.where(b >= LANES, hi, lo).astype(jnp.uint32)


def _shift(a, s: int):
    """``a`` moved ``s`` positions later along the row-major stream.

    out[r, l] = a[r, l - s], taking lanes below ``s`` from the end of row
    r - 1 (row 0 wraps around and must not be used).
    """
    moved = pltpu.roll(a, s, axis=1)  # lanes l - s, same row
    above = pltpu.roll(moved, 1, axis=0)  # the same, one row up
    lane = jax.lax.broadcasted_iota(jnp.int32, a.shape, 1)
    return jnp.where(lane >= s, moved, above)


def _hash_tile(p, cur_ref, prev_ref, gear_ref):
    """Shared kernel body: the (ROWS, LANES) gear hashes of grid cell ``p``.

    The 32-tap sum is built by doubling: after the step of shift s each
    position holds the weighted sum of its last 2s gear values, so five
    shifts stand for the 32 taps.
    """
    gear = gear_ref[...]  # (2, LANES) int32: table entries 0-127, 128-255
    g = _lookup(gear, cur_ref[...].astype(jnp.int32))  # (ROWS, LANES)
    # history: the previous tile's last HALO_ROWS rows; only their last
    # 31 positions reach this tile, and what the doubling drags in from
    # before them (or from the row-0 wrap) stays inside the history rows
    prev = prev_ref[...].astype(jnp.int32)[-HALO_ROWS:]
    hist = _lookup(gear, prev)
    # first tile has no history: it contributes nothing
    hist = jnp.where(p == 0, jnp.uint32(0), hist)
    a = jnp.concatenate([hist, g])  # (HALO_ROWS + ROWS, LANES)
    s = 1
    while s < WINDOW:
        a = a + (_shift(a, s) << jnp.uint32(s))
        s *= 2
    return a[HALO_ROWS:]


def _fire_kernel(cur_ref, prev_ref, gear_ref, mask_ref, out_ref):
    """Fused hash + boundary test: emit the fire bitmap, not the hashes.

    The mask test runs on the still-VMEM-resident hash vector, so only a
    1-byte-per-position bool bitmap ships back to the host instead of the
    4-byte uint32 hash array (the staged path's round-trip).
    """
    h = _hash_tile(pl.program_id(0), cur_ref, prev_ref, gear_ref)
    out_ref[...] = (h & mask_ref[...][0]) == 0


def _stream_specs():
    """BlockSpecs of the (rows, LANES) stream, its history block and the
    table; the history block of cell 0 is clamped to block 0 and masked."""
    per_tile = ROWS // HALO_BLOCK
    return [
        pl.BlockSpec((ROWS, LANES), lambda p: (p, 0)),
        pl.BlockSpec((HALO_BLOCK, LANES),
                     lambda p: (jnp.maximum(p * per_tile - 1, 0), 0)),
        pl.BlockSpec((2, LANES), lambda p: (0, 0)),
    ]


@functools.partial(jax.jit, static_argnames=("interpret",))
def _gear_fire_padded(data: jnp.ndarray, gear: jnp.ndarray,
                      mask: jnp.ndarray, *,
                      interpret: bool) -> jnp.ndarray:
    TRACES.gear += 1  # trace-time only: one increment per compiled shape
    n = data.shape[0]
    rows = data.reshape(-1, LANES)
    return pl.pallas_call(
        _fire_kernel,
        grid=(n // TILE,),
        in_specs=[*_stream_specs(),
                  pl.BlockSpec((1,), lambda p: (0,))],
        out_specs=pl.BlockSpec((ROWS, LANES), lambda p: (p, 0)),
        out_shape=jax.ShapeDtypeStruct(rows.shape, jnp.bool_),
        interpret=interpret,
        # the kernel's instruction name in a device trace, kept if this
        # wrapper is renamed (the benchmark's rooflines match it)
        name="_gear_fire_padded",
    )(rows, rows, gear, mask).reshape(n)


def gear_fire(data, mask, *, interpret: bool) -> jnp.ndarray:
    """(N,) uint8 + boundary mask -> (N,) bool fire bitmap (one launch).

    The fused twin of :func:`gear_hash`: hash and mask test both run on
    device, so the result is the boolean candidate bitmap (pad positions
    are sliced off like the hash path).  Returns the *device* array
    unmaterialized -- callers overlap host work with the launch and
    compact to positions with ``np.flatnonzero`` when they resolve it.
    """
    data = jnp.asarray(data, jnp.uint8)
    n = data.shape[0]
    if n == 0:
        return jnp.zeros((0,), jnp.bool_)
    mask_arr = jnp.asarray([mask], jnp.uint32)
    return _gear_fire_padded(pad_to_bucket(data), _device_gear_table(),
                             mask_arr, interpret=interpret)[:n]


@functools.partial(jax.jit, static_argnames=("interpret",))
def _gear_hash_padded(data: jnp.ndarray, gear: jnp.ndarray, *,
                      interpret: bool) -> jnp.ndarray:
    TRACES.gear += 1  # trace-time only: one increment per compiled shape
    n = data.shape[0]
    rows = data.reshape(-1, LANES)
    return pl.pallas_call(
        _kernel,
        grid=(n // TILE,),
        in_specs=_stream_specs(),
        out_specs=pl.BlockSpec((ROWS, LANES), lambda p: (p, 0)),
        out_shape=jax.ShapeDtypeStruct(rows.shape, jnp.uint32),
        interpret=interpret,
        # the kernel's instruction name in a device trace, kept if this
        # wrapper is renamed (the benchmark's rooflines match it)
        name="_gear_hash_padded",
    )(rows, rows, gear).reshape(n)


def gear_hash(data, *, interpret: bool) -> jnp.ndarray:
    """(N,) uint8 -> (N,) uint32 gear hash (kernel entry point).

    Input is zero-padded to ``bucket_len(n)`` so repeated calls with
    varying lengths reuse a bounded set of compiled launches; zero pad
    bytes only influence hash positions >= n, which are sliced off (the
    gear window looks strictly backward).
    """
    data = jnp.asarray(data, jnp.uint8)
    n = data.shape[0]
    if n == 0:
        return jnp.zeros((0,), jnp.uint32)
    return _gear_hash_padded(pad_to_bucket(data), _device_gear_table(),
                             interpret=interpret)[:n]
