"""BBHash-style minimal perfect hash function (§3.3/§4.2, paper's [20]).

Construction (host, numpy): a cascade of bit-vector levels of size
``gamma * |unresolved|``.  At each level every unresolved key hashes to one
position; positions hit exactly once become set bits (those keys are
resolved), collided keys fall through to the next level.  Keys left after
``max_levels`` go to a tiny sorted fallback array.

The minimal hash of a key resolved at level L with bit position p is
``rank(bits, level_offset[L] + p)`` — the number of set bits before it in
the concatenated level bit-vectors; fallback keys get the tail indices.

Query (device, :func:`lookup_arrs`): a handful of XLA gathers + popcounts
over a flat u32 word array with a sampled rank directory — no
deserialization, mirroring the paper's mmap layout.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

import jax.numpy as jnp

from .hashing import np_seeded_hash32

GAMMA_DEFAULT = 2.0
MAX_LEVELS_DEFAULT = 12
RANK_BLOCK_WORDS = 8  # one rank sample per 8 u32 words (256 bits)
_LEVEL_SEED = 0x5EED1E5


def _level_seed(level: int) -> int:
    return (_LEVEL_SEED * (level + 1)) & 0xFFFFFFFF


@dataclass
class MPHF:
    """Flat-buffer MPHF; all arrays are plain numpy and jnp-convertible."""
    words: np.ndarray            # (W,) uint32 concatenated level bit-vectors
    level_word_offset: np.ndarray  # (L+1,) int32 word offset of each level
    level_bits: np.ndarray       # (L,) int32 m_l — bit-vector size per level
    block_rank: np.ndarray       # (ceil(W/8),) uint32 popcount before block
    fallback_fps: np.ndarray     # (F,) uint32 sorted fingerprints
    fallback_idx: np.ndarray     # (F,) int64 minimal-hash values
    n_keys: int
    n_rank_bits: int             # set bits across levels (= n_keys - F)

    @property
    def n_levels(self) -> int:
        return len(self.level_bits)

    def size_bits(self) -> int:
        return (self.words.size * 32 + self.block_rank.size * 32
                + self.fallback_fps.size * 96
                + self.level_word_offset.size * 32 + self.level_bits.size * 32)

    # ---- numpy batch query ---------------------------------------------------
    def lookup_np(self, fps: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        """Returns (idx int64, definitely_absent bool).  For keys in the
        construction set, idx is their unique minimal hash.  For other keys
        idx is arbitrary unless definitely_absent is True."""
        fps = np.asarray(fps, dtype=np.uint32)
        idx = np.zeros(fps.shape, dtype=np.int64)
        found = np.zeros(fps.shape, dtype=bool)
        for lvl in range(self.n_levels):
            m = int(self.level_bits[lvl])
            if m == 0:
                continue
            pos = np_seeded_hash32(fps, _level_seed(lvl)) % np.uint32(m)
            gbit = pos.astype(np.int64) + (int(self.level_word_offset[lvl]) << 5)
            word = gbit >> 5
            hit = (self.words[word] >> (gbit & 31).astype(np.uint32)) & 1
            hit = hit.astype(bool) & ~found
            if hit.any():
                idx[hit] = self._rank_np(gbit[hit])
                found |= hit
        # fallback
        if self.fallback_fps.size:
            fpos = np.searchsorted(self.fallback_fps, fps)
            fpos = np.minimum(fpos, self.fallback_fps.size - 1)
            fhit = (self.fallback_fps[fpos] == fps) & ~found
            idx[fhit] = self.fallback_idx[fpos[fhit]]
            found |= fhit
        return idx, ~found

    # ---- scalar query (single-token fast path) -----------------------------
    def lookup_scalar(self, fp: int) -> tuple[int, bool]:
        """Pure-python-int probe: ~5 us/key vs ~1 ms for a 1-element numpy
        batch (per-call dispatch overhead).  Measured 40x on the paper's
        term(ID) scenario — EXPERIMENTS.md §Perf (sketch)."""
        from .hashing import scalar_seeded_hash32
        words = self.words
        for lvl in range(self.n_levels):
            m = int(self.level_bits[lvl])
            if m == 0:
                continue
            pos = scalar_seeded_hash32(fp, _level_seed(lvl)) % m
            gbit = pos + (int(self.level_word_offset[lvl]) << 5)
            w = gbit >> 5
            if (int(words[w]) >> (gbit & 31)) & 1:
                block = w >> 3
                r = int(self.block_rank[block])
                for j in range(block << 3, w):
                    r += int(words[j]).bit_count()
                r += (int(words[w]) & ((1 << (gbit & 31)) - 1)).bit_count()
                return r, False
        if self.fallback_fps.size:
            p = int(np.searchsorted(self.fallback_fps, np.uint32(fp)))
            if p < self.fallback_fps.size \
                    and int(self.fallback_fps[p]) == fp:
                return int(self.fallback_idx[p]), False
        return 0, True

    def _rank_np(self, gbit: np.ndarray) -> np.ndarray:
        """Rank of a set bit: sampled block rank + popcounts of the residual
        words, fully vectorized — one (N, 8) gather + popcount for the words
        before the target, one masked popcount for the partial word (the old
        per-word loop paid 8 gathers and 16 popcount passes per batch)."""
        gbit = np.asarray(gbit, dtype=np.int64)
        word = gbit >> 5
        block = word >> 3
        base = block << 3
        cols = base[:, None] + np.arange(RANK_BLOCK_WORDS, dtype=np.int64)
        pc = _popcount32_np(self.words[np.minimum(cols, self.words.size - 1)])
        before = cols < word[:, None]
        part = _popcount32_np(
            self.words[word]
            & ((np.uint32(1) << (gbit & 31).astype(np.uint32)) - np.uint32(1)))
        return (self.block_rank[block].astype(np.int64)
                + (pc * before).sum(axis=1) + part)

    # ---- jnp batch query -------------------------------------------------------
    def device_arrays(self) -> dict:
        # fb_count makes the fallback resolution data-driven (one traced
        # body whether or not this MPHF has fallback keys); the empty pad
        # is 0xFFFFFFFF so padded fallback arrays stay sorted when stacked
        # segments pad to a common length.
        return dict(
            words=jnp.asarray(self.words),
            block_rank=jnp.asarray(self.block_rank),
            level_word_offset=jnp.asarray(self.level_word_offset),
            level_bits=jnp.asarray(self.level_bits),
            fallback_fps=jnp.asarray(
                self.fallback_fps if self.fallback_fps.size else
                np.full(1, 0xFFFFFFFF, np.uint32)),
            fallback_idx=jnp.asarray(
                (self.fallback_idx if self.fallback_idx.size else
                 np.zeros(1, np.int64)).astype(np.int32)),
            fb_count=jnp.asarray(self.fallback_fps.size, jnp.int32),
        )

    def lookup_jnp(self, fps, arrs=None):
        """Device lookup (:func:`lookup_arrs`) over this MPHF's arrays."""
        if arrs is None:
            arrs = self.device_arrays()
        return lookup_arrs(
            fps, arrs, level_bits=tuple(int(x) for x in self.level_bits),
            level_word_offset=tuple(int(x) for x in self.level_word_offset))


def lookup_arrs(fps, arrs, *, level_bits: tuple, level_word_offset: tuple):
    """THE device MPHF lookup, a jnp mirror of :meth:`MPHF.lookup_np`:
    (idx int32, absent bool) for a batch of fingerprints.

    Only the level layout is static; everything else comes from ``arrs``
    (a :meth:`MPHF.device_arrays` dict, or a zero-padded row of a stacked
    per-shard buffer).  Each level costs two XLA gathers over arrays in
    HBM: the probed word's whole 8-word rank block, (N, 8) at once, and
    its sampled block rank.  Fallback keys resolve against the sorted
    ``fallback_fps``, guarded by the dynamic ``fb_count`` so padded or
    fallback-less rows never match."""
    from .hashing import seeded_hash32
    words, block_rank = arrs["words"], arrs["block_rank"]
    fps = jnp.asarray(fps).astype(jnp.uint32)
    lane = jnp.arange(RANK_BLOCK_WORDS, dtype=jnp.int32)
    idx = jnp.zeros(fps.shape, dtype=jnp.int32)
    found = jnp.zeros(fps.shape, dtype=bool)
    for lvl, m in enumerate(level_bits):
        if m == 0:
            continue
        pos = seeded_hash32(fps, _level_seed(lvl)) % jnp.uint32(m)
        gbit = pos.astype(jnp.int32) + (int(level_word_offset[lvl]) << 5)
        word = (gbit >> 5)[:, None]
        cols = ((word >> 3) << 3) + lane                         # (N, 8)
        blk = words[jnp.minimum(cols, words.shape[0] - 1)]
        wv = jnp.max(jnp.where(cols == word, blk, jnp.uint32(0)), axis=1)
        bit = (gbit & 31).astype(jnp.uint32)
        hit = ((wv >> bit) & 1).astype(bool) & ~found
        before = jnp.where(cols < word, jax_popcount(blk), jnp.uint32(0))
        rank = (block_rank[word[:, 0] >> 3].astype(jnp.int32)
                + jnp.sum(before, axis=1).astype(jnp.int32)
                + jax_popcount(wv & ((jnp.uint32(1) << bit) - jnp.uint32(1)))
                .astype(jnp.int32))
        idx = jnp.where(hit, rank, idx)
        found = found | hit
    fb_fps, fb_idx = arrs["fallback_fps"], arrs["fallback_idx"]
    fpos = jnp.clip(jnp.searchsorted(fb_fps, fps), 0, fb_fps.shape[0] - 1)
    fhit = (fb_fps[fpos] == fps) & (fpos < arrs["fb_count"]) & ~found
    idx = jnp.where(fhit, fb_idx[fpos], idx)
    return idx, ~(found | fhit)


def jax_popcount(x):
    import jax.lax as lax
    return lax.population_count(x.astype(jnp.uint32))


def _popcount32_np(x: np.ndarray) -> np.ndarray:
    x = x.astype(np.uint32, copy=True)
    x = x - ((x >> np.uint32(1)) & np.uint32(0x55555555))
    x = (x & np.uint32(0x33333333)) + ((x >> np.uint32(2)) & np.uint32(0x33333333))
    x = (x + (x >> np.uint32(4))) & np.uint32(0x0F0F0F0F)
    return ((x * np.uint32(0x01010101)) >> np.uint32(24)).astype(np.int64)


def build_mphf(keys: np.ndarray, *, gamma: float = GAMMA_DEFAULT,
               max_levels: int = MAX_LEVELS_DEFAULT) -> MPHF:
    keys = np.unique(np.asarray(keys, dtype=np.uint32))
    unresolved = keys
    level_words: list[np.ndarray] = []
    level_bits: list[int] = []
    assigned_key_order: list[np.ndarray] = []  # keys resolved per level
    assigned_pos: list[np.ndarray] = []
    for lvl in range(max_levels):
        if unresolved.size == 0:
            break
        m = int(np.ceil(gamma * unresolved.size))
        m = max(256, ((m + 255) // 256) * 256)  # word+block aligned
        pos = np_seeded_hash32(unresolved, _level_seed(lvl)) % np.uint32(m)
        counts = np.bincount(pos, minlength=m)
        once = counts == 1
        hit = once[pos]
        words = np.zeros(m >> 5, dtype=np.uint32)
        set_pos = pos[hit].astype(np.int64)
        np.bitwise_or.at(words, set_pos >> 5,
                         (np.uint32(1) << (set_pos & 31).astype(np.uint32)))
        level_words.append(words)
        level_bits.append(m)
        assigned_key_order.append(unresolved[hit])
        assigned_pos.append(set_pos)
        unresolved = unresolved[~hit]

    words = (np.concatenate(level_words) if level_words
             else np.zeros(8, dtype=np.uint32))
    # pad to a whole rank block
    pad = (-len(words)) % RANK_BLOCK_WORDS
    if pad:
        words = np.concatenate([words, np.zeros(pad, np.uint32)])
    level_word_offset = np.zeros(len(level_bits) + 1, dtype=np.int32)
    for i, m in enumerate(level_bits):
        level_word_offset[i + 1] = level_word_offset[i] + (m >> 5)

    pop = _popcount32_np(words)
    cum = np.concatenate([[0], np.cumsum(pop)]).astype(np.uint32)
    block_rank = cum[:-1][::RANK_BLOCK_WORDS].copy()
    n_rank_bits = int(cum[-1])

    fallback_order = np.argsort(unresolved, kind="stable")
    fallback_fps = unresolved[fallback_order]
    fallback_idx = (n_rank_bits + np.arange(unresolved.size)).astype(np.int64)
    # indices must follow sorted-fp order for reproducibility
    fallback_idx = fallback_idx  # already aligned with sorted order

    return MPHF(words=words,
                level_word_offset=level_word_offset,
                level_bits=np.asarray(level_bits, dtype=np.int32),
                block_rank=block_rank,
                fallback_fps=fallback_fps,
                fallback_idx=fallback_idx,
                n_keys=int(keys.size),
                n_rank_bits=n_rank_bits)
