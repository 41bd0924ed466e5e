"""Batched SHA-1 Pallas kernel.

SHA-1 is sequential over the 64-byte blocks of one message but fully
parallel across messages, so the TPU mapping is lane-parallel: the
message axis is laid out last -- (M, 16, B) words, one message per lane --
and each grid cell processes TILE_B messages; the 80-round compression
runs unrolled on (TILE_B,)-wide uint32 rows (VPU logical/rotate/add ops)
and a ``fori_loop`` walks the message blocks.  Messages shorter than the
padded block count are masked per-lane via ``counts``.

Input comes from :func:`repro.core.hashing.sha1_pad_batch` (standard SHA-1
padding done host-side); output digests match ``hashlib.sha1`` bit-exactly.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core.hashing import SHA1_H0, SHA1_K
from repro.kernels.launches import TRACES

TILE_B = 128  # messages per grid cell

_H0 = SHA1_H0.astype(np.int64)
_K = SHA1_K.astype(np.int64)


def _rotl(x, c):
    return (x << jnp.uint32(c)) | (x >> jnp.uint32(32 - c))


def _compress(h, words):
    """h: 5-tuple of (TILE_B,) uint32; words: (16, TILE_B) uint32."""
    w = [words[t] for t in range(16)]
    for t in range(16, 80):
        w.append(_rotl(w[t - 3] ^ w[t - 8] ^ w[t - 14] ^ w[t - 16], 1))
    a, b, c, d, e = h
    for t in range(80):
        if t < 20:
            f, k = (b & c) | (~b & d), jnp.uint32(_K[0])
        elif t < 40:
            f, k = b ^ c ^ d, jnp.uint32(_K[1])
        elif t < 60:
            f, k = (b & c) | (b & d) | (c & d), jnp.uint32(_K[2])
        else:
            f, k = b ^ c ^ d, jnp.uint32(_K[3])
        tmp = _rotl(a, 5) + f + e + k + w[t]
        e, d, c, b, a = d, c, _rotl(b, 30), a, tmp
    return tuple(x + y for x, y in zip(h, (a, b, c, d, e)))


def _kernel(blocks_ref, counts_ref, out_ref, *, n_blocks: int):
    counts = counts_ref[0]  # (TILE_B,)
    h0 = tuple(jnp.full((counts.shape[0],), jnp.uint32(_H0[i]))
               for i in range(5))

    def body(m, h):
        words = blocks_ref[m]  # (16, TILE_B)
        upd = _compress(h, words)
        live = m < counts
        return tuple(jnp.where(live, u, x) for u, x in zip(upd, h))

    h = jax.lax.fori_loop(0, n_blocks, body, h0)
    out_ref[...] = jnp.stack(h)  # (5, TILE_B)


@functools.partial(jax.jit, static_argnames=("interpret", "tile"))
def _sha1_padded(blocks: jnp.ndarray, counts: jnp.ndarray, *,
                 interpret: bool, tile: int = TILE_B) -> jnp.ndarray:
    TRACES.sha1 += 1  # trace-time only: one increment per compiled shape
    B, M, _ = blocks.shape
    # messages on lanes: laid out messages-first, (B, M, 16), a window-
    # scale batch (4096 x 81 blocks) ran out of scoped VMEM on a v5e
    return pl.pallas_call(
        functools.partial(_kernel, n_blocks=M),
        grid=(B // tile,),
        in_specs=[
            pl.BlockSpec((M, 16, tile), lambda b: (0, 0, b)),
            pl.BlockSpec((1, tile), lambda b: (0, b)),
        ],
        out_specs=pl.BlockSpec((5, tile), lambda b: (0, b)),
        out_shape=jax.ShapeDtypeStruct((5, B), jnp.uint32),
        interpret=interpret,
    )(blocks.transpose(1, 2, 0), counts.reshape(1, B)).T


def sha1_digest_words(blocks, counts, *, interpret: bool) -> jnp.ndarray:
    """(B, M, 16) uint32 padded blocks + (B,) counts -> (B, 5) digests.

    Batches of at least TILE_B messages pad to a TILE_B multiple and run
    lane-parallel per grid cell; smaller batches pad to the next power of
    two and run as one narrower cell, so a short steady-state window does
    not drag TILE_B-wide dead lanes through the 80-round compression.
    Either way the compiled-shape set stays bounded (powers of two up to
    TILE_B, then TILE_B-quantized grids).
    """
    blocks = jnp.asarray(blocks, jnp.uint32)
    counts = jnp.asarray(counts, jnp.int32).reshape(-1, 1)
    B = blocks.shape[0]
    if B >= TILE_B:
        tile, padded = TILE_B, B + ((-B) % TILE_B)
    else:
        tile = padded = 1 << max(0, B - 1).bit_length()
    pad = padded - B
    if pad:
        blocks = jnp.pad(blocks, ((0, pad), (0, 0), (0, 0)))
        counts = jnp.pad(counts, ((0, pad), (0, 0)))
    out = _sha1_padded(blocks, counts, interpret=interpret, tile=tile)
    return out[:B]
