"""Compressed static function: minimal-hash -> posting-list rank (§3.3).

Posting lists are ranked by reference count (rank 0 = most referenced).
The rank of entry ``i`` is encoded with ``floor(log2(max(rank,1))) + 1``
bits — *not* uniquely decodable on its own; decodability comes from storing
every entry's bit length in a packed 5-bit array plus a sampled absolute
prefix-sum directory, exactly as the paper describes.

Query path: one sampled-offset gather + a <=SAMPLE-length 5-bit prefix sum
+ a two-word bit-field gather.  Fully vectorized in numpy and jnp.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

import jax.numpy as jnp

from .bitio import BitWriter, np_peek_bits

SAMPLE = 32          # prefix-sum sampling interval (configurable, §3.3)
LEN_BITS = 5         # rank < 2^30 -> code length <= 31 -> 5-bit lengths
_BLOCK_LEN_WORDS = SAMPLE * LEN_BITS // 32   # words of one block's lengths


def code_length(rank: np.ndarray) -> np.ndarray:
    """floor(log2(max(rank,1))) + 1 bits per value."""
    r = np.maximum(np.asarray(rank, dtype=np.int64), 1)
    return np.floor(np.log2(r)).astype(np.int64) + 1


@dataclass
class CompressedStaticFunction:
    bitseq: np.ndarray       # (W,) uint32 concatenated variable-length codes
    lengths: np.ndarray      # (ceil(N*5/32),) uint32 packed 5-bit lengths
    samples: np.ndarray      # (ceil(N/SAMPLE),) int64 absolute bit offsets
    n: int

    def size_bits(self) -> int:
        return 32 * (self.bitseq.size + self.lengths.size) + 64 * self.samples.size

    # ---- host/vectorized decode ------------------------------------------------
    def get_np(self, idx: np.ndarray) -> np.ndarray:
        idx = np.asarray(idx, dtype=np.int64)
        block = idx // SAMPLE
        base = block * SAMPLE
        off = self.samples[block].copy()
        lens_all = np.empty((idx.size, SAMPLE), dtype=np.int64)
        for j in range(SAMPLE):
            lens_all[:, j] = self._len_np(np.minimum(base + j, self.n - 1))
        rel = idx - base
        for j in range(SAMPLE):
            off += np.where(j < rel, lens_all[:, j], 0)
        nbits = lens_all[np.arange(idx.size), rel]
        return np_peek_bits(self.bitseq, off, nbits).astype(np.int64)

    def get_scalar(self, idx: int) -> int:
        """Single-entry decode with python ints (query fast path)."""
        from .bitio import peek_bits
        block = idx // SAMPLE
        base = block * SAMPLE
        off = int(self.samples[block])
        for j in range(base, idx):
            off += peek_bits(self.lengths, min(j, self.n - 1) * LEN_BITS,
                             LEN_BITS)
        nbits = peek_bits(self.lengths, idx * LEN_BITS, LEN_BITS)
        return peek_bits(self.bitseq, off, nbits)

    def _len_np(self, idx: np.ndarray) -> np.ndarray:
        bit = idx * LEN_BITS
        return np_peek_bits(self.lengths, bit,
                            np.full(idx.shape, LEN_BITS, np.int64)).astype(np.int64)

    # ---- device decode -----------------------------------------------------------
    def device_arrays(self) -> dict:
        return dict(bitseq=jnp.asarray(self.bitseq),
                    lengths=jnp.asarray(self.lengths),
                    samples=jnp.asarray(self.samples.astype(np.int32)))

    def get_jnp(self, idx, arrs=None):
        if arrs is None:
            arrs = self.device_arrays()
        return csf_get_jnp(idx, arrs)


def csf_get_jnp(idx, arrs):
    """Decode ``idx`` against a :meth:`CompressedStaticFunction.device_arrays`
    dict.  Nothing about the layout is static, so the same traced body
    serves a single sketch and a stacked per-shard row.

    A sample block's SAMPLE packed lengths fill whole words, so one
    (N, _BLOCK_LEN_WORDS) gather fetches all of them and the prefix sum
    runs on static bit fields of that tile."""
    bitseq, lengths, samples = arrs["bitseq"], arrs["lengths"], arrs["samples"]
    idx = idx.astype(jnp.int32)
    block = idx // SAMPLE
    rel = idx - block * SAMPLE
    cols = block[:, None] * _BLOCK_LEN_WORDS \
        + jnp.arange(_BLOCK_LEN_WORDS, dtype=jnp.int32)
    tile = lengths[jnp.minimum(cols, lengths.shape[0] - 1)]
    off = samples[block]
    nbits = jnp.zeros(idx.shape, dtype=jnp.int32)
    for j in range(SAMPLE):
        w, sh = divmod(j * LEN_BITS, 32)
        field = tile[:, w] >> jnp.uint32(sh)
        if sh + LEN_BITS > 32:
            field = field | (tile[:, w + 1] << jnp.uint32(32 - sh))
        lj = (field & jnp.uint32((1 << LEN_BITS) - 1)).astype(jnp.int32)
        off = off + jnp.where(j < rel, lj, 0)
        nbits = jnp.where(j == rel, lj, nbits)
    return _jnp_peek_var(bitseq, off, nbits).astype(jnp.int32)


def _jnp_peek_var(words, bitpos, nbits):
    word = bitpos >> 5
    off = (bitpos & 31).astype(jnp.uint32)
    w0 = words[word]
    w1 = words[jnp.minimum(word + 1, words.shape[0] - 1)]
    lo = (w0 >> off)
    hi = jnp.where(off > 0, w1 << (jnp.uint32(32) - off), jnp.uint32(0))
    v = lo | hi
    mask = jnp.where(nbits >= 32, jnp.uint32(0xFFFFFFFF),
                     (jnp.uint32(1) << nbits.astype(jnp.uint32)) - jnp.uint32(1))
    return v & mask


def build_csf(values: np.ndarray) -> CompressedStaticFunction:
    """Encode ``values[i]`` (the rank for minimal hash i)."""
    values = np.asarray(values, dtype=np.int64)
    n = values.size
    lens = code_length(values)
    # code bit-sequence
    w = BitWriter()
    samples = []
    for i in range(n):
        if i % SAMPLE == 0:
            samples.append(w.bitpos)
        w.write(int(values[i]), int(lens[i]))
    bitseq = w.array()
    # packed 5-bit lengths
    lw = BitWriter()
    for i in range(n):
        lw.write(int(lens[i]), LEN_BITS)
    return CompressedStaticFunction(
        bitseq=bitseq, lengths=lw.array(),
        samples=np.asarray(samples if samples else [0], dtype=np.int64), n=n)
