"""The immutable DynaWarp sketch (§3.3/§4.2): MPHF + signatures +
compressed static function + BIC posting lists, in a single flat buffer.

Build pipeline (host):
  SealedContent -> rank lists by reference count -> MPHF over fingerprints
  -> CSF(minimal hash -> rank) -> signature bits -> BIC bit stream.

Query pipeline:
  * host   : Algorithm 3 via numpy (query.py)
  * device : batched jnp probe over the flat uint32 buffers,
             plus optional dense bitmap planes for on-device boolean
             algebra across query tokens (TPU adaptation, DESIGN.md §3).
"""
from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np

import jax.numpy as jnp

from . import bic
from .bitio import np_peek_bits, pack_bitmap_planes, pack_fixed_width
from .csf import CompressedStaticFunction, build_csf
from .hashing import np_seeded_hash32, scalar_seeded_hash32, token_fingerprint
from .mphf import MPHF, build_mphf
from .mutable_sketch import SealedContent

SIG_SEED = 0x516E4715
DEFAULT_SIG_BITS = 8
DEFAULT_PLANE_BUDGET = 64 << 20  # bytes of optional device bitmap planes

# Process-global device-cache registries keyed by DURABLE segment id
# ("<abs file path>@g<generation>", assigned by the manifest-based store).
# RAM-only sketches memoize on the object as before; durable sketches share
# these registries so reopening a store in the same process re-uploads
# nothing it already staged — the id, not Python object identity, names the
# uploaded buffers.  Entries are dropped with the segment files (compaction
# orphan GC calls drop_device_cache / discard_durable_caches).
_DURABLE_DEVICE_CACHES: dict[str, dict] = {}
_DURABLE_ROW_CACHES: dict[str, dict] = {}
_DURABLE_SHARD_SLOTS: dict[str, int] = {}


def discard_durable_caches(durable_id_or_path: str) -> None:
    """Free every registry entry of a durable segment id — or, given a bare
    file path, of EVERY generation of that path (orphan GC deletes files;
    a later path reuse must never see stale buffers)."""
    prefix = durable_id_or_path + "@"
    for reg in (_DURABLE_DEVICE_CACHES, _DURABLE_ROW_CACHES,
                _DURABLE_SHARD_SLOTS):
        for k in [k for k in reg
                  if k == durable_id_or_path or k.startswith(prefix)]:
            del reg[k]


@dataclass
class ImmutableSketch:
    mphf: MPHF
    csf: CompressedStaticFunction
    signatures: np.ndarray      # packed sig_bits-wide signatures by min-hash
    sig_bits: int
    bic_bits: np.ndarray        # u32 BIC stream of all deduplicated lists
    bic_offsets: np.ndarray     # (L+1,) int64 bit offsets (rank -> offset)
    bic_counts: np.ndarray      # (L,) int64 postings per list
    n_postings: int
    n_tokens: int
    planes: np.ndarray | None = None   # (L, ceil(P/32)) u32 device bitmaps
    stats: dict = field(default_factory=dict)
    # Retained SealedContent (full fingerprints + lists) when the segment
    # must stay mergeable by the cold-segment compactor; MPHFs alone are
    # not mergeable.  Excluded from size accounting (host-side scratch).
    sealed_source: SealedContent | None = None
    # Durable segment id ("<abs path>@g<gen>") once the manifest-based
    # store has published this segment to disk; keys the process-global
    # device-cache registries instead of object identity.
    durable_id: str | None = None

    # ------------------------------------------------------------------ sizes
    @property
    def n_lists(self) -> int:
        return len(self.bic_counts)

    def size_bits(self, *, include_planes: bool = False) -> int:
        total = (self.mphf.size_bits() + self.csf.size_bits()
                 + self.signatures.size * 32
                 + self.bic_bits.size * 32
                 + self.bic_offsets.size * 64 + self.bic_counts.size * 16)
        if include_planes and self.planes is not None:
            total += self.planes.size * 32
        return total

    def size_bytes(self, **kw) -> int:
        return (self.size_bits(**kw) + 7) // 8

    # ------------------------------------------------------------------ query
    def probe_fingerprints_np(self, fps: np.ndarray
                              ) -> tuple[np.ndarray, np.ndarray]:
        """Batched membership probe.  Returns (present bool, rank int64);
        rank is only meaningful where present."""
        fps = np.asarray(fps, dtype=np.uint32)
        idx, absent = self.mphf.lookup_np(fps)
        idx = np.clip(idx, 0, max(self.n_tokens - 1, 0))
        sig = self._sig_at_np(idx)
        want = np_seeded_hash32(fps, SIG_SEED) & np.uint32((1 << self.sig_bits) - 1)
        present = (~absent) & (sig == want) & (self.n_tokens > 0)
        rank = np.where(present, self.csf.get_np(idx), 0)
        return present, rank

    def probe_fp_scalar(self, fp: int) -> tuple[bool, int]:
        """Single-fingerprint probe on the python-int fast path (Alg. 3
        inner loop): MPHF -> signature -> CSF rank.  Avoids per-call numpy
        dispatch (~40x for needle queries, EXPERIMENTS.md §Perf)."""
        from .bitio import peek_bits
        from .hashing import scalar_seeded_hash32
        if self.n_tokens == 0:
            return False, 0
        idx, absent = self.mphf.lookup_scalar(fp)
        if absent:
            return False, 0
        idx = min(idx, self.n_tokens - 1)
        sig = peek_bits(self.signatures, idx * self.sig_bits, self.sig_bits)
        want = scalar_seeded_hash32(fp, SIG_SEED) & ((1 << self.sig_bits) - 1)
        if sig != want:
            return False, 0
        return True, self.csf.get_scalar(idx)

    def _sig_at_np(self, idx: np.ndarray) -> np.ndarray:
        bitpos = idx.astype(np.int64) * self.sig_bits
        return np_peek_bits(self.signatures, bitpos,
                            np.full(idx.shape, self.sig_bits, np.int64))

    def postings_for_rank(self, rank: int) -> np.ndarray:
        return bic.decode_list(self.bic_bits, self.bic_offsets,
                               self.bic_counts, int(rank), self.n_postings)

    def query_token(self, token: bytes) -> np.ndarray | None:
        """Host single-token query: None if definitely/probably absent."""
        fp = np.asarray([token_fingerprint(token)], dtype=np.uint32)
        present, rank = self.probe_fingerprints_np(fp)
        if not present[0]:
            return None
        return self.postings_for_rank(int(rank[0]))

    # ---------------------------------------------------------------- device
    def device_arrays(self) -> dict:
        arrs = dict(self.mphf.device_arrays())
        arrs.update({f"csf_{k}": v for k, v in self.csf.device_arrays().items()})
        arrs["signatures"] = jnp.asarray(self.signatures)
        # dynamic clip bounds: the probe body reads them from the dict, so
        # one traced graph serves this sketch and any padded stacked row
        arrs["n_tokens1"] = jnp.asarray(max(self.n_tokens - 1, 0), jnp.int32)
        if self.planes is not None:
            arrs["planes"] = jnp.asarray(self.planes)
            arrs["n_lists1"] = jnp.asarray(max(self.n_lists - 1, 0),
                                           jnp.int32)
        return arrs

    def device_cache(self) -> dict:
        """Memoized :meth:`device_arrays` — the per-segment device cache of
        the batched query engine.  The flat sketch buffers are uploaded on
        first use and reused by every later query wave in the process.
        Durable segments (published by the manifest-based store) memoize in
        a process-global registry keyed by :attr:`durable_id`, so a store
        reopened in the same process re-uploads nothing it already staged."""
        if self.durable_id is not None:
            arrs = _DURABLE_DEVICE_CACHES.get(self.durable_id)
            if arrs is None:
                arrs = _DURABLE_DEVICE_CACHES[self.durable_id] = \
                    self.device_arrays()
            return arrs
        arrs = getattr(self, "_device_cache_arrs", None)
        if arrs is None:
            arrs = self.device_arrays()
            self._device_cache_arrs = arrs
        return arrs

    def has_device_cache(self) -> bool:
        """Whether this segment's flat buffers are already staged on device
        (the engines' upload accounting — durable-id aware)."""
        if self.durable_id is not None:
            return self.durable_id in _DURABLE_DEVICE_CACHES
        return getattr(self, "_device_cache_arrs", None) is not None

    def device_row_cache(self, key, device, build) -> tuple[dict, bool]:
        """Per-(layout, device) memo of this segment's padded shard row —
        the sharded-engine counterpart of :meth:`device_cache`.  ``build``
        returns the padded HOST arrays; they are uploaded to ``device``
        on first use and reused by every later wave AND by every engine
        rebuild (compaction keeps unchanged segments' shard buffers;
        durable segments key the registry by :attr:`durable_id`, so even a
        reopened store's rows stay staged).  Returns (arrays, uploaded_now)."""
        import jax
        if self.durable_id is not None:
            cache = _DURABLE_ROW_CACHES.setdefault(self.durable_id, {})
        else:
            cache = getattr(self, "_device_row_caches", None)
            if cache is None:
                cache = self._device_row_caches = {}
        k = (key, getattr(device, "id", device))
        arrs = cache.get(k)
        if arrs is not None:
            return arrs, False
        arrs = {name: jax.device_put(v, device)
                for name, v in build().items()}
        cache[k] = arrs
        return arrs, True

    def get_shard_slot(self) -> int | None:
        """Stable shard placement (durable-id aware): a segment keeps the
        slot it was first given so its uploaded rows survive engine
        rebuilds AND store reopens within one process."""
        if self.durable_id is not None:
            return _DURABLE_SHARD_SLOTS.get(self.durable_id)
        return getattr(self, "_shard_slot", None)

    def set_shard_slot(self, slot: int) -> None:
        if self.durable_id is not None:
            _DURABLE_SHARD_SLOTS[self.durable_id] = int(slot)
        else:
            self._shard_slot = int(slot)

    def drop_device_cache(self) -> None:
        """Invalidate the memoized device arrays (called on segments merged
        away by compaction so their device buffers can be freed).  A durable
        segment also loses its registry identity: its file is about to be
        GC'd, and an in-flight wave still probing it (background compaction)
        must fall back to the per-object memo — re-inserting under the dead
        durable id would leak the upload for the rest of the process."""
        self._device_cache_arrs = None
        self._device_row_caches = None
        if self.durable_id is not None:
            discard_durable_caches(self.durable_id)
            self.durable_id = None

    def _level_layout(self) -> tuple[tuple, tuple]:
        """Static MPHF level metadata — the shard/layout bucket key."""
        return (tuple(int(x) for x in self.mphf.level_bits),
                tuple(int(x) for x in self.mphf.level_word_offset))

    def probe_fingerprints_jnp(self, fps, arrs=None):
        """Device probe (:func:`probe_tokens_from`) of this sketch; mirrors
        :meth:`probe_fingerprints_np`."""
        if arrs is None:
            arrs = self.device_arrays()
        lb, lo = self._level_layout()
        return probe_tokens_from(fps, arrs, level_bits=lb,
                                 level_word_offset=lo, sig_bits=self.sig_bits)

    def match_bitmap_jnp(self, fps, arrs=None):
        """(Q, W) u32 posting bitmaps per query fingerprint; absent tokens
        yield all-zero rows.  Requires bitmap planes."""
        if self.planes is None:
            raise ValueError("bitmap planes were not built for this sketch")
        if arrs is None:
            arrs = self.device_arrays()
        lb, lo = self._level_layout()
        return match_bitmap_from(fps, arrs, level_bits=lb,
                                 level_word_offset=lo, sig_bits=self.sig_bits)


def _resolve_probe(fps, idx, absent, arrs, sig_bits: int):
    """Minimal-hash -> (present, rank): signature check + CSF decode.
    Every bound is data (``n_tokens1``, ``csf_n1``), so the traced body is
    layout-independent past the MPHF lookup."""
    from .hashing import seeded_hash32
    idx = jnp.clip(idx, 0, arrs["n_tokens1"])
    bitpos = idx * sig_bits
    sig = _jnp_peek_fixed(arrs["signatures"], bitpos, sig_bits)
    want = seeded_hash32(fps, SIG_SEED) & jnp.uint32((1 << sig_bits) - 1)
    present = (~absent) & (sig == want)
    csf_arrs = {k[len("csf_"):]: v for k, v in arrs.items()
                if k.startswith("csf_")}
    from .csf import csf_get_jnp
    rank = jnp.where(present, csf_get_jnp(idx, csf_arrs), 0)
    return present, rank


def probe_tokens_from(fps, arrs, *, level_bits: tuple,
                      level_word_offset: tuple, sig_bits: int):
    """THE device probe code path (MPHF lookup + signature check + CSF
    rank), parameterized by an ``ImmutableSketch.device_arrays`` dict.
    The single-device engine passes a segment's own arrays; the sharded
    engine passes a zero-padded row sliced from a stacked per-shard buffer
    — both produce bit-identical (present, rank)."""
    from .mphf import lookup_arrs
    fps = fps.astype(jnp.uint32)
    idx, absent = lookup_arrs(fps, arrs, level_bits=level_bits,
                              level_word_offset=level_word_offset)
    return _resolve_probe(fps, idx, absent, arrs, sig_bits)


def match_bitmap_from(fps, arrs, *, level_bits: tuple,
                      level_word_offset: tuple, sig_bits: int):
    """(Q, W) u32 posting bitmaps via :func:`probe_tokens_from` + plane
    gather; absent tokens (and all-zero padded rows) yield zero rows."""
    present, rank = probe_tokens_from(fps, arrs, level_bits=level_bits,
                                      level_word_offset=level_word_offset,
                                      sig_bits=sig_bits)
    rows = arrs["planes"][jnp.clip(rank, 0, arrs["n_lists1"])]
    return jnp.where(present[:, None], rows, jnp.uint32(0))


def _jnp_peek_fixed(words, bitpos, nbits: int):
    word = bitpos >> 5
    off = (bitpos & 31).astype(jnp.uint32)
    w0 = words[word]
    w1 = words[jnp.minimum(word + 1, words.shape[0] - 1)]
    lo = w0 >> off
    hi = jnp.where(off > 0, w1 << (jnp.uint32(32) - off), jnp.uint32(0))
    return (lo | hi) & jnp.uint32((1 << nbits) - 1)


# ---------------------------------------------------------------------- build
def build_immutable(content: SealedContent, *,
                    sig_bits: int = DEFAULT_SIG_BITS,
                    plane_budget_bytes: int = DEFAULT_PLANE_BUDGET,
                    gamma: float = 2.0) -> ImmutableSketch:
    n_tokens = len(content.fps)
    n_lists = len(content.lists)
    # 1. rank lists by reference count, descending (§3.3)
    order = np.argsort(-content.refcounts, kind="stable")
    rank_of_list = np.empty(n_lists, dtype=np.int64)
    rank_of_list[order] = np.arange(n_lists)
    token_ranks = rank_of_list[content.list_ids] if n_tokens else \
        np.empty(0, np.int64)

    # 2. MPHF over fingerprints
    mphf = build_mphf(content.fps, gamma=gamma)
    if n_tokens:
        idx, absent = mphf.lookup_np(content.fps)
        assert not absent.any(), "MPHF must resolve every construction key"
        assert len(np.unique(idx)) == n_tokens, "MPHF must be injective"
    else:
        idx = np.empty(0, np.int64)

    # 3. CSF of ranks in minimal-hash order
    values_mh = np.zeros(max(n_tokens, 1), dtype=np.int64)
    values_mh[idx] = token_ranks
    csf = build_csf(values_mh[:n_tokens] if n_tokens else np.zeros(1, np.int64))

    # 4. signature bits in minimal-hash order
    sigs_tok = np_seeded_hash32(content.fps, SIG_SEED) \
        & np.uint32((1 << sig_bits) - 1)
    sigs_mh = np.zeros(max(n_tokens, 1), dtype=np.uint32)
    sigs_mh[idx] = sigs_tok
    signatures = pack_fixed_width(sigs_mh[:max(n_tokens, 1)], sig_bits)

    # 5. BIC-encode lists in rank order
    lists_by_rank = [content.lists[i] for i in order]
    bic_bits, bic_offsets, bic_counts = bic.encode_lists(
        lists_by_rank, content.n_postings)

    # 6. optional device bitmap planes (vectorized scatter over all lists)
    planes = None
    words = (max(content.n_postings, 1) + 31) // 32
    if n_lists and n_lists * words * 4 <= plane_budget_bytes:
        planes = pack_bitmap_planes(lists_by_rank, content.n_postings)

    stats = dict(content.stats)
    stats.update(n_tokens=n_tokens, n_lists=n_lists,
                 n_postings=content.n_postings,
                 dedup_ratio=(1.0 - n_lists / n_tokens) if n_tokens else 0.0)
    return ImmutableSketch(
        mphf=mphf, csf=csf, signatures=signatures, sig_bits=sig_bits,
        bic_bits=bic_bits, bic_offsets=bic_offsets, bic_counts=bic_counts,
        n_postings=content.n_postings, n_tokens=n_tokens, planes=planes,
        stats=stats)
