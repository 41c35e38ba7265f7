"""Batched device query engine — the store-facing read path.

The paper's Algorithm 3 is a sequential host loop; the engine evaluates
*waves* of queries fully on-device — one probe dispatch per segment plus
one reduce dispatch per wave:

  * **Per-segment device cache** — every segment's flat sketch buffers
    (:meth:`ImmutableSketch.device_cache`) are uploaded once per process
    and reused by all later waves; queries stream only fingerprints.
  * **Shape-bucketed batching** — Q queries x T token fingerprints are
    packed into padded (Q_bucket, T_bucket) arrays (powers of two), so
    repeated waves hit one jit cache entry per bucket shape.  The MPHF
    lookup is XLA gathers (``core.mphf.lookup_arrs``) and the T-axis
    boolean reduction runs through the Pallas ``bitset_ops`` kernel.
  * **Multi-segment fan-out** — per-spill immutable segments stay
    queryable (no monolithic merge): each segment contributes per-token
    posting bitmaps, OR-ed across segments before the AND/OR consumer.
    A token's posting set is the union of its per-segment sets, so the
    fan-out result is bit-identical to the merged-sketch result.
  * **Host fallback** — segments built without bitmap planes (plane
    budget exceeded) are probed on the host, with an LRU cache of
    decoded BIC posting lists, and their bitmaps OR-ed into the wave.
  * **Device candidate extraction** — the combined hit bitmaps compact
    into posting-id lists on device (Pallas ``bitmap_extract`` kernel),
    so only a (Q, max_hits) id tensor crosses to host; the
    host-mode fallback decodes rows via an LRU-cached flatnonzero word
    decode instead of a full ``np.unpackbits`` bit matrix.

Semantics match ``query.query_and`` / ``query_or`` exactly: an absent
token zeroes its bitmap (AND -> empty), an empty query returns empty.
"""
from __future__ import annotations

from collections import OrderedDict

import numpy as np

import jax
import jax.numpy as jnp

from .. import tracing
from .hashing import token_fingerprint

_MIN_Q_BUCKET = 8
_MIN_T_BUCKET = 1
_MIN_HITS_BUCKET = 8


def _bucket(n: int, lo: int) -> int:
    """Next power of two >= max(n, lo)."""
    return 1 << (max(n, lo) - 1).bit_length()


def _as_fp(tok) -> int:
    if isinstance(tok, (bytes, bytearray)):
        return token_fingerprint(tok)
    return int(tok)


class QueryEngine:
    """Evaluates query waves against one or more immutable segments."""

    def __init__(self, segments, *, n_postings: int | None = None,
                 lru_lists: int = 4096,
                 extract_on_device: bool | None = None):
        self.segments = [s for s in segments if s.n_tokens > 0]
        # Batched waves compact hit bitmaps into posting-id lists on
        # device (kernels/bitmap_extract): only the (Q, max_hits) id
        # tensor crosses to host.  ``False`` keeps extraction on the
        # host via the LRU-cached flatnonzero word decode.
        self._extract_on_device = (True if extract_on_device is None
                                   else extract_on_device)
        if n_postings is None:
            n_postings = max((s.n_postings for s in self.segments),
                             default=0)
        self.n_postings = int(n_postings)
        self.words = (max(self.n_postings, 1) + 31) // 32
        self._plane_segs = [(si, s) for si, s in enumerate(self.segments)
                            if s.planes is not None]
        self._host_segs = [(si, s) for si, s in enumerate(self.segments)
                           if s.planes is None]
        self._seg_fns: dict[int, object] = {}
        self._reduce_fns: dict[str, object] = {}
        self._extract_fns: dict[int, object] = {}
        self._lru: OrderedDict[tuple, np.ndarray] = OrderedDict()
        self._lru_cap = lru_lists
        # host-extraction LRU of decoded bitmap rows (keyed by content),
        # alongside the BIC posting-list LRU above
        self._bm_lru: OrderedDict[bytes, np.ndarray] = OrderedDict()
        self.compile_count = 0      # jit traces (one per bucket shape)
        self.upload_count = 0       # segment device-cache uploads

    # ------------------------------------------------------------- public
    def query(self, tokens, *, op: str = "and") -> np.ndarray:
        """Single query: posting ids (sorted int64) matching Alg. 3.

        Latency-aware dispatch: a lone query runs the scalar host probe
        (microseconds, reusing the engine's LRU of decoded BIC lists)
        rather than paying a device wave's dispatch latency; batches go
        through :meth:`query_batch`'s device wave."""
        return self.host_query(tokens, op=op)

    def query_batch(self, token_lists, *, op: str = "and"
                    ) -> list[np.ndarray]:
        """A wave of queries; ``token_lists[i]`` is query i's tokens."""
        return self.query_fps_batch(
            [[_as_fp(t) for t in toks] for toks in token_lists], op=op)

    def query_fps_batch(self, fps_lists, *, op: str = "and"
                        ) -> list[np.ndarray]:
        """Core wave evaluation over integer fingerprints."""
        if op not in ("and", "or"):
            raise ValueError(f"op={op!r}")
        n_queries = len(fps_lists)
        # empty queries resolve to empty immediately (Alg. 3 semantics)
        results: list = [np.empty(0, np.int64)] * n_queries
        live = [i for i, fps in enumerate(fps_lists) if len(fps)]
        if not live or not self.segments or self.n_postings == 0:
            return [np.empty(0, np.int64) for _ in range(n_queries)]

        fps_pad, mask = self._pack(fps_lists, live)
        bitmaps, counts = self._evaluate(fps_pad, mask, op)
        postings = self._extract(bitmaps, counts[:len(live)])
        for out, i in zip(postings, live):
            results[i] = out
        return results

    # ------------------------------------------------------------ packing
    def _pack(self, fps_lists, live):
        lens = np.asarray([len(fps_lists[i]) for i in live], np.int64)
        tb = _bucket(int(lens.max()), _MIN_T_BUCKET)
        qb = _bucket(len(live), _MIN_Q_BUCKET)
        fps = np.zeros((qb, tb), dtype=np.uint32)
        mask = np.zeros((qb, tb), dtype=bool)
        total = int(lens.sum())
        flat = np.fromiter((fp for i in live for fp in fps_lists[i]),
                           dtype=np.uint64, count=total).astype(np.uint32)
        rows = np.repeat(np.arange(len(live)), lens)
        cols = np.arange(total) - np.repeat(np.cumsum(lens) - lens, lens)
        fps[rows, cols] = flat
        mask[rows, cols] = True
        return fps, mask

    # --------------------------------------------------------- evaluation
    def _evaluate(self, fps: np.ndarray, mask: np.ndarray, op: str):
        """(Qb, Tb) wave -> ((Qb, W) device uint32 bitmaps, (Qb,) counts).

        Per-token plane accumulation (:meth:`_device_token_planes` — the
        hook the sharded engine overrides), an OR of any host-fallback
        contribution, then one reduce dispatch folding the T axis.  The
        combined bitmaps STAY on device for the extraction stage; only
        the per-query counts come back here."""
        fps_dev = jnp.asarray(fps)
        acc = self._device_token_planes(fps_dev)
        host_acc = None     # host-fallback contribution
        for si, seg in self._host_segs:
            rows = self._host_token_planes(si, seg, fps, mask)
            host_acc = rows if host_acc is None else host_acc | rows
        if host_acc is not None:
            h = jnp.asarray(host_acc)
            acc = h if acc is None else acc | h
        combined, counts = self._reduce_fn(op)(acc, jnp.asarray(mask))
        with tracing.span(tracing.WAVE_SYNC, what="counts"):
            return combined, np.asarray(counts)

    def _device_token_planes(self, fps_dev):
        """(Qb, Tb) device fps -> (Qb, Tb, W) OR-accumulated token planes
        over the plane-backed segments: one probe dispatch per segment
        (keeping every segment's compiled graph small and its jit cache
        independent of the fleet size)."""
        acc = None
        for si, seg in self._plane_segs:
            rows = self._seg_fn(si)(fps_dev, self._seg_arrs(seg))
            acc = rows if acc is None else acc | rows
        return acc

    def _seg_arrs(self, seg):
        had = seg.has_device_cache()
        arrs = seg.device_cache()
        if not had:
            self.upload_count += 1
        return arrs

    def _seg_fn(self, si: int):
        """Jitted per-segment probe: (Qb, Tb) fps -> (Qb, Tb, W) token
        bitmaps (MPHF lookup + signature check + CSF rank + plane
        gather), padded to the engine-global bitmap width."""
        fn = self._seg_fns.get(si)
        if fn is None:
            seg = self.segments[si]
            out_w = self.words

            def copr_probe(fps2d, arrs):
                self.compile_count += 1          # runs once per trace
                q, t = fps2d.shape
                rows = seg.match_bitmap_jnp(fps2d.reshape(-1), arrs)
                rows = rows.reshape(q, t, -1)[:, :, :out_w]
                pad = out_w - rows.shape[-1]
                if pad > 0:
                    rows = jnp.pad(rows, ((0, 0), (0, 0), (0, pad)))
                return rows

            fn = jax.jit(copr_probe)
            self._seg_fns[si] = fn
        return fn

    def _reduce_fn(self, op: str):
        """Jitted wave consumer: neutralize pad slots, fold the T axis
        and popcount through the Pallas ``bitset_ops`` kernel."""
        fn = self._reduce_fns.get(op)
        if fn is None:
            def copr_reduce(planes, mask):
                from ..kernels.bitset_ops.ops import bitset_reduce_batch
                self.compile_count += 1
                neutral = jnp.uint32(0xFFFFFFFF if op == "and" else 0)
                planes = jnp.where(mask[:, :, None], planes, neutral)
                return bitset_reduce_batch(planes, op=op)

            fn = jax.jit(copr_reduce)
            self._reduce_fns[op] = fn
        return fn

    # ------------------------------------------------------ host fallback
    def _host_token_planes(self, si: int, seg, fps: np.ndarray,
                           mask: np.ndarray) -> np.ndarray:
        """Host-side (Qb, Tb, W) bitmaps for a plane-less segment, with an
        LRU of decoded BIC posting lists shared across waves."""
        qb, tb = fps.shape
        rows = np.zeros((qb, tb, self.words), dtype=np.uint32)
        flat_fps, inverse = np.unique(fps[mask], return_inverse=True)
        if flat_fps.size == 0:
            return rows
        present, rank = seg.probe_fingerprints_np(flat_fps)
        fp_rows = np.zeros((flat_fps.size, self.words), dtype=np.uint32)
        for j in np.flatnonzero(present):
            postings = self._cached_postings(si, seg, int(rank[j]))
            np.bitwise_or.at(fp_rows[j], postings >> 5,
                             np.uint32(1) << (postings & 31)
                             .astype(np.uint32))
        # scatter only the real (masked) slots, via the unique-inverse map
        q_idx, t_idx = np.nonzero(mask)
        rows[q_idx, t_idx] = fp_rows[inverse]
        return rows

    def _cached_postings(self, si: int, seg, rank: int) -> np.ndarray:
        key = (si, rank)
        hit = self._lru.get(key)
        if hit is not None:
            self._lru.move_to_end(key)
            return hit
        postings = seg.postings_for_rank(rank)
        self._lru[key] = postings
        if len(self._lru) > self._lru_cap:
            self._lru.popitem(last=False)
        return postings

    # --------------------------------------------------------- extraction
    def _extract(self, bitmaps, counts: np.ndarray) -> list[np.ndarray]:
        """Bitmap -> posting-id compaction for a whole wave.

        ``bitmaps`` is the (Qb, W) device array straight out of the
        reduce; ``counts`` covers only the live rows.  Device mode (the
        default) runs the ``bitmap_extract`` compaction on device and
        transfers one (Qb, max_hits) id tensor; host mode decodes rows
        through the LRU-cached flatnonzero word decode — neither path
        materializes a full (Q, 32*W) bit matrix anywhere."""
        n = len(counts)
        out: list[np.ndarray] = [np.empty(0, np.int64)] * n
        nz = np.flatnonzero(counts > 0)
        if nz.size == 0:
            return out
        if self._extract_on_device:
            # the full (Qb, W) wave is compacted, pad rows included: Qb
            # is already the power-of-two bucket of the live count, so
            # slicing to the live rows would save under 2x only on
            # sub-minimum waves while re-tracing per distinct count
            max_hits = _bucket(int(counts.max()), _MIN_HITS_BUCKET)
            ids = self._extract_fn(max_hits)(bitmaps)
            with tracing.span(tracing.WAVE_SYNC, what="ids"):
                ids = np.asarray(ids)
            for i in nz:
                out[int(i)] = ids[int(i), :int(counts[int(i)])] \
                    .astype(np.int64)
            return out
        rows = np.asarray(bitmaps[:n])
        for i in nz:
            out[int(i)] = self._decode_bitmap_host(rows[int(i)])
        return out

    def _extract_fn(self, max_hits: int):
        """Jitted device compaction: (Qb, W) bitmaps -> (Qb, max_hits)
        posting ids, -1-padded.  ``max_hits`` is bucketed (power of two)
        by the caller so repeated waves reuse the same trace."""
        fn = self._extract_fns.get(max_hits)
        if fn is None:
            def copr_extract(bitmaps):
                from ..kernels.bitmap_extract.ops import bitmap_extract
                self.compile_count += 1
                ids, _ = bitmap_extract(bitmaps, max_hits=max_hits)
                return ids

            fn = jax.jit(copr_extract)
            self._extract_fns[max_hits] = fn
        return fn

    def _decode_bitmap_host(self, row: np.ndarray) -> np.ndarray:
        """Posting ids of one (W,) uint32 bitmap row, via flatnonzero over
        the non-empty words only (no full bit-matrix expansion), LRU-cached
        by row content so repeated needles skip the decode."""
        key = row.tobytes()
        hit = self._bm_lru.get(key)
        if hit is not None:
            self._bm_lru.move_to_end(key)
            return hit
        w_idx = np.flatnonzero(row)
        if w_idx.size == 0:
            ids = np.empty(0, np.int64)
        else:
            sub, lane = np.nonzero(
                (row[w_idx][:, None] >> np.arange(32, dtype=np.uint32)) & 1)
            ids = (w_idx[sub].astype(np.int64) << 5) + lane
            ids = ids[ids < self.n_postings]
        self._bm_lru[key] = ids
        if len(self._bm_lru) > self._lru_cap:
            self._bm_lru.popitem(last=False)
        return ids

    # ------------------------------------------------------------ replicas
    def clone(self) -> "QueryEngine":
        """A cheap serving replica over the same segments: shares every
        per-segment device cache (keyed process-globally for durable
        segments, per-sketch otherwise) but owns its jit caches and
        LRUs, so the serving layer can run concurrent waves on separate
        replicas without cross-wave locking."""
        return QueryEngine(self.segments, n_postings=self.n_postings,
                           lru_lists=self._lru_cap,
                           extract_on_device=self._extract_on_device)

    # ------------------------------------------------------------- sizing
    def index_bytes(self, **kw) -> int:
        return sum(s.size_bytes(**kw) for s in self.segments)

    # ----------------------------------------------------- host scalar path
    def host_query(self, tokens, *, op: str = "and") -> np.ndarray:
        """Scalar host path with identical fan-out semantics (per-token
        union across segments, then AND/OR): the single-query fast path
        and the property-test oracle for the device waves.  Decoded BIC
        posting lists go through the engine's LRU, so repeated needles
        skip the decode entirely."""
        fps = [_as_fp(t) for t in tokens]
        if not fps:
            return np.empty(0, np.int64)
        per_token = []
        for fp in fps:
            parts = []
            for si, seg in enumerate(self.segments):
                pres, rk = seg.probe_fp_scalar(fp)
                if pres:
                    parts.append(self._cached_postings(si, seg, int(rk)))
            per_token.append(
                np.unique(np.concatenate(parts)) if parts
                else np.empty(0, np.int64))
        acc = per_token[0]
        for p in per_token[1:]:
            acc = (np.intersect1d(acc, p, assume_unique=True)
                   if op == "and" else np.union1d(acc, p))
        return acc.astype(np.int64)
