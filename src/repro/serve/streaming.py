"""Streaming mutable index: crash-consistent online inserts/deletes with
delta-layer search and background compaction (DESIGN.md §15).

``retrieval.build_index`` is build-once: any corpus change forces a full
rebuild, and a crash mid-rebuild leaves nothing to serve.  ``MutableIndex``
wraps the immutable main index with the three mechanisms a mutable serving
corpus needs:

  1. **Delta layer.**  Inserts land in a fixed-capacity append buffer
     (``delta_capacity`` slots — static shapes, so every search program is
     compiled once per configuration).  Searches brute-force the delta with
     the pairwise distance kernel and, once the buffer passes
     ``delta_graph_min`` occupancy, additionally beam-search a small
     incrementally rebuilt Vamana over the delta prefix (rebuilt on
     occupancy doublings — amortized O(log C) rebuilds per fill).  Delta
     candidates fold into the main-graph ef-pool through the same
     ``search._merge_topk`` pool merge the in-loop pool update uses, under
     the existing bit-pinned tie rule (main-pool entries win distance
     ties).

  2. **Tombstone deletes.**  Deleting a main-graph vector records its row
     in an id-keyed tombstone set; ``search.apply_tombstones`` masks those
     rows out of the merged ef-wide pool before the k truncation on every
     execution strategy (unsharded, scatter-gather, routed, fused-routed),
     so a deleted id never surfaces even while its node still anchors
     graph walks.  Deleting a delta vector just kills its slot.

  3. **Write-ahead log + generational snapshots.**  With ``wal_dir`` set,
     every insert/delete is appended as an fsync'd checksummed record
     (``checkpoint.append_framed``) BEFORE it is acknowledged; ``load``
     restores the newest committed snapshot generation and replays the
     WAL, so a process kill at any byte offset recovers exactly the acked
     mutation prefix — a torn final record fails its length/crc frame and
     is refused, never half-applied.  Compaction rolls the generation:
     snapshot + sidecar are written first, the pointer JSON is the atomic
     commit record written LAST, and the old generation's files are
     removed only after the pointer lands (a crash mid-compaction leaves
     the old generation fully intact).

  4. **Background compaction.**  ``maybe_compact`` (and a full delta at
     insert time) rebuilds the affected shards off the search path —
     shards owning tombstones or receiving delta vectors are rebuilt by
     the compiled build step (DESIGN.md §12); untouched shards keep their
     graph arrays byte-for-byte and are restacked through
     ``graph.assemble_sharded`` — then hot-swaps into a running engine via
     ``ResilientSearcher.swap_index`` (which resets the latency governor;
     serving reads the old generation until the swap commits).  Searches
     NEVER rebuild anything: the hot path is search + merge only.

The healthy state (empty delta, no tombstones, pre-compaction) dispatches
``retrieval.retrieval_attention_batched`` on the wrapped index directly —
bit-identical serving through the exact cached programs of a static index
(pinned by tests/test_streaming.py).

External ids are stable across compaction: the wrapped index's rows 0..n-1
become external ids 0..n-1 and inserts continue the sequence; result pools
come back in external-id space.
"""
from __future__ import annotations

import dataclasses
import functools
import json
import os
import struct

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import graph as graph_lib
from repro.core import metric as metric_lib
from repro.core import search as search_lib
from repro.core import vamana as vamana_lib
from repro.core.graph import INVALID
from repro.kernels import ops
from repro.serve import retrieval as retrieval_lib
from repro.train import checkpoint as ckpt_lib

STREAM_FORMAT = 1
# Streaming runtime artifacts (like resilience's snapshot suffixes): never
# repo content — tools/check_repo.py rejects any tracked file matching
# these suffixes (suffix-sync pinned by tests/test_repo.py).
WAL_SUFFIX = ".wal"
STREAM_STATE = ".stream.npz"
STREAM_POINTER = ".stream.json"
STREAM_SUFFIXES = (WAL_SUFFIX, STREAM_STATE, STREAM_POINTER)

# Delta occupancy at which a small Vamana is built over the delta prefix;
# below it brute force over <= delta_graph_min vectors is cheaper than a
# graph walk (one fused matmul vs ~ef gather rounds).
DELTA_GRAPH_MIN = 128

# Tombstone device arrays pad up to a multiple of this so the set of
# compiled tombstones=True program shapes stays small while deletes accrue.
TOMB_BLOCK_MULT = 16

_OP_INSERT, _OP_DELETE = 1, 2
_INS_HDR = struct.Struct("<BQiI")        # op, seq, ext_id, dim
_DEL_REC = struct.Struct("<BQi")         # op, seq, ext_id


def _encode_insert(seq: int, ext: int, key: np.ndarray,
                   value: np.ndarray) -> bytes:
    return (_INS_HDR.pack(_OP_INSERT, seq, ext, key.size)
            + key.astype(np.float32).tobytes()
            + value.astype(np.float32).tobytes())


def _encode_delete(seq: int, ext: int) -> bytes:
    return _DEL_REC.pack(_OP_DELETE, seq, ext)


def _decode(body: bytes) -> tuple:
    """Decode one WAL record body -> ("insert", seq, ext, key, value) |
    ("delete", seq, ext).  Raises ValueError on any structural mismatch —
    the frame layer already checksummed the bytes, so a failure here means
    a format bug, not a torn write."""
    op = body[0]
    if op == _OP_INSERT:
        _, seq, ext, dim = _INS_HDR.unpack_from(body)
        want = _INS_HDR.size + 2 * 4 * dim
        if len(body) != want:
            raise ValueError(
                f"insert record is {len(body)} bytes, expected {want}")
        vecs = np.frombuffer(body, np.float32, count=2 * dim,
                             offset=_INS_HDR.size)
        return ("insert", seq, ext, vecs[:dim].copy(), vecs[dim:].copy())
    if op == _OP_DELETE:
        _, seq, ext = _DEL_REC.unpack_from(body)
        if len(body) != _DEL_REC.size:
            raise ValueError(
                f"delete record is {len(body)} bytes, expected "
                f"{_DEL_REC.size}")
        return ("delete", seq, ext)
    raise ValueError(f"unknown WAL opcode {op}")


@functools.lru_cache(maxsize=8)
def _delta_brute_fn(kernel: str, kc: int):
    """jit'd delta brute-force: top-kc live slots at offset >= lo.

    ``lo`` (traced) excludes the graph-searched prefix so graph-pool and
    brute candidates stay disjoint (a duplicate id entering ``_merge_topk``
    twice would surface twice).  Returns (slot ids int32[b, kc] INVALID-
    padded, dists, live-slot count int32[] — the per-query #dist the
    brute pass costs).

    Bounded cache: the buffers are *arguments* (never closed over), but
    each cached entry still pins its compiled executable and the jit
    machinery's references; serving sweeps only a handful of (kernel, kc)
    shapes, so 8 entries cover steady state while an adversarial ef sweep
    can no longer grow the cache without bound.
    """

    @jax.jit
    def run(qs, dvecs, live, lo):
        d = ops.pairwise_distance(qs, dvecs, kernel)          # (b, C)
        ok = live & (jnp.arange(dvecs.shape[0]) >= lo)
        d = jnp.where(ok[None, :], d, jnp.inf)
        neg, idx = jax.lax.top_k(-d, kc)     # ties prefer the lower slot
        dist = -neg
        ids = jnp.where(jnp.isfinite(dist), idx.astype(jnp.int32), INVALID)
        dist = jnp.where(ids == INVALID, jnp.inf, dist)
        return ids, dist, jnp.sum(ok).astype(jnp.int32)
    return run


class MutableIndex:
    """A mutable serving index: immutable main graph + delta + tombstones.

    Construct with ``wrap`` (fresh, from a built RetrievalIndex) or
    ``load`` (crash recovery: newest snapshot generation + WAL replay).
    ``attention_batched`` has the calling convention of
    ``retrieval.retrieval_attention_batched``, so a MutableIndex drops
    into ``ResilientSearcher`` / ``ServeEngine.attach_retrieval`` directly
    (the searcher duck-dispatches to it); result pools are in stable
    external-id space.

    Single-writer by design (like ``ServeEngine``'s tick loop): mutations
    and searches interleave on one thread; compaction runs off the search
    path but in-process.
    """

    def __init__(self, index, *, wal_dir: str | None = None,
                 delta_capacity: int = 1024,
                 tombstone_compact_frac: float = 0.2,
                 delta_graph_min: int = DELTA_GRAPH_MIN,
                 build_fn=None, tag: str = "index",
                 main_ext: np.ndarray | None = None,
                 _gen: int = 0, _applied_seq: int = 0,
                 _next_ext: int | None = None):
        if delta_capacity < 1:
            raise ValueError(
                f"delta_capacity={delta_capacity} must be >= 1")
        if not 0.0 < tombstone_compact_frac <= 1.0:
            raise ValueError(
                f"tombstone_compact_frac={tombstone_compact_frac} must be "
                f"in (0, 1]")
        self.main = index
        self._met = metric_lib.resolve(index.metric)
        self.delta_capacity = int(delta_capacity)
        self.tombstone_compact_frac = float(tombstone_compact_frac)
        self.delta_graph_min = int(delta_graph_min)
        self._build = build_fn or self._default_build
        self.wal_dir = wal_dir
        self.tag = tag
        self.gen = int(_gen)
        self.compactions = 0
        n = int(index.keys.shape[0])
        dh = int(index.keys.shape[1])
        self.n_main = n
        self.main_ext = (np.arange(n, dtype=np.int32) if main_ext is None
                         else np.asarray(main_ext, np.int32))
        if self.main_ext.shape != (n,):
            raise ValueError(
                f"main_ext shape {self.main_ext.shape} != ({n},)")
        self._ext_identity = bool(
            np.array_equal(self.main_ext, np.arange(n, dtype=np.int32)))
        self._loc: dict[int, tuple[str, int]] = {
            int(e): ("m", r) for r, e in enumerate(self.main_ext)}
        self._next_ext = (int(self.main_ext.max(initial=-1)) + 1
                          if _next_ext is None else int(_next_ext))
        self._next_seq = int(_applied_seq) + 1
        C = self.delta_capacity
        self._d_keys = np.zeros((C, dh), np.float32)
        self._d_vals = np.zeros((C, dh), np.float32)
        self._d_search = np.zeros((C, dh), np.float32)
        self._d_ext = np.full(C, INVALID, np.int32)
        self._d_live = np.zeros(C, bool)
        self._d_occ = 0
        self._dg_ids = None          # delta-prefix Vamana adjacency (device)
        self._dg_entry = 0
        self._dg_n = 0
        self._tomb_ext: set[int] = set()
        self._tomb_version = 0
        self._tomb_cache: tuple[int, jax.Array | None] = (-1, None)
        self._dirty = True
        self._cat_idx = None
        self._cat_ext_dev = None
        self._main_ext_dev = None
        self._d_search_dev = None
        self._d_live_dev = None

    # -- construction -------------------------------------------------------

    @classmethod
    def wrap(cls, index, **kw) -> "MutableIndex":
        """Wrap a freshly built RetrievalIndex as generation 0.

        With ``wal_dir`` set, persists the generation-0 snapshot and commit
        pointer immediately, so a crash before the first mutation already
        recovers to the wrapped state."""
        mi = cls(index, **kw)
        if mi.wal_dir is not None:
            mi._persist_generation()
        return mi

    @classmethod
    def load(cls, wal_dir: str, *, mesh=None, tag: str = "index",
             **kw) -> "MutableIndex":
        """Crash recovery: newest committed generation + WAL replay.

        Reads the pointer (the atomic commit record — absent or stale
        pointers mean the matching generation never committed), restores
        that generation's index snapshot and external-id sidecar, then
        replays every complete WAL record with ``seq > applied_seq``.  The
        WAL file is truncated to its last complete record, so a torn tail
        is both refused now and physically gone before the next append.
        """
        from repro.serve import resilience as resilience_lib
        ptr_path = os.path.join(wal_dir, tag + STREAM_POINTER)
        if not os.path.exists(ptr_path):
            raise FileNotFoundError(
                f"no stream pointer {ptr_path}: nothing committed here "
                f"(a crash before the first wrap() persists leaves no "
                f"state to recover)")
        with open(ptr_path) as f:
            ptr = json.load(f)
        if ptr.get("format") != STREAM_FORMAT:
            raise ValueError(
                f"stream format {ptr.get('format')!r} != supported "
                f"{STREAM_FORMAT} ({ptr_path})")
        gen = int(ptr["gen"])
        gtag = f"{tag}-g{gen}"
        index = resilience_lib.load_index(wal_dir, tag=gtag, mesh=mesh)
        with np.load(os.path.join(wal_dir, gtag + STREAM_STATE)) as z:
            main_ext = z["main_ext"]
        mi = cls(index, wal_dir=wal_dir, tag=tag, main_ext=main_ext,
                 _gen=gen, _applied_seq=int(ptr["applied_seq"]),
                 _next_ext=int(ptr["next_ext"]), **kw)
        wal_path = mi._wal_path()
        if os.path.exists(wal_path):
            bodies, good = ckpt_lib.read_framed(wal_path)
            expect = int(ptr["applied_seq"]) + 1
            for body in bodies:
                rec = _decode(body)
                if rec[1] != expect:
                    raise ValueError(
                        f"WAL seq {rec[1]} != expected {expect}: the log "
                        f"is not the committed generation's suffix")
                expect += 1
                if rec[0] == "insert":
                    mi._apply_insert(rec[2], rec[3], rec[4])
                else:
                    mi._apply_delete(rec[2])
                mi._next_seq = expect
            with open(wal_path, "rb+") as f:
                f.truncate(good)
        return mi

    # -- properties ---------------------------------------------------------

    @property
    def num_shards(self) -> int:
        return self.main.num_shards

    @property
    def delta_count(self) -> int:
        """Allocated delta slots (live + dead) — the compaction trigger."""
        return self._d_occ

    @property
    def delta_live(self) -> int:
        return int(self._d_live[:self._d_occ].sum())

    @property
    def tombstone_count(self) -> int:
        return len(self._tomb_ext)

    @property
    def tombstone_fraction(self) -> float:
        return len(self._tomb_ext) / max(1, self.n_main)

    @property
    def pristine(self) -> bool:
        """No delta slots and no tombstones: serving can dispatch the
        wrapped index's own cached programs unchanged."""
        return self._d_occ == 0 and not self._tomb_ext

    @property
    def live_count(self) -> int:
        return self.n_main - len(self._tomb_ext) + self.delta_live

    # -- mutation -----------------------------------------------------------

    def insert(self, key, value=None) -> int:
        """Durably insert one vector; returns its stable external id.

        The WAL record is fsync'd BEFORE the in-memory apply — when this
        returns, the insert survives a kill at any later instant.  A full
        delta buffer compacts first (off the search path: searches never
        trigger this).
        """
        key = np.asarray(key, np.float32).reshape(-1)
        dh = self.main.keys.shape[1]
        if key.shape != (dh,):
            raise ValueError(
                f"key shape {key.shape} != ({dh},): one vector per insert")
        value = (key if value is None
                 else np.asarray(value, np.float32).reshape(-1))
        if value.shape != (dh,):
            raise ValueError(f"value shape {value.shape} != ({dh},)")
        if self._d_occ >= self.delta_capacity:
            self.compact()
        ext, seq = self._next_ext, self._next_seq
        if self.wal_dir is not None:
            ckpt_lib.append_framed(self._wal_path(),
                                   _encode_insert(seq, ext, key, value))
        self._apply_insert(ext, key, value)
        self._next_ext = ext + 1
        self._next_seq = seq + 1
        return ext

    def delete(self, ext_id: int) -> None:
        """Durably delete by external id (WAL-first, like ``insert``).

        Main-graph vectors become tombstones — still graph nodes, never
        surfaced (``apply_tombstones`` at merge time); delta vectors just
        lose their slot.  Unknown or already-deleted ids raise KeyError
        before anything is logged.
        """
        ext_id = int(ext_id)
        if ext_id not in self._loc:
            raise KeyError(
                f"external id {ext_id} is not live (never inserted, or "
                f"already deleted)")
        seq = self._next_seq
        if self.wal_dir is not None:
            ckpt_lib.append_framed(self._wal_path(),
                                   _encode_delete(seq, ext_id))
        self._apply_delete(ext_id)
        self._next_seq = seq + 1

    def _apply_insert(self, ext: int, key: np.ndarray,
                      value: np.ndarray) -> None:
        if self._d_occ >= self.delta_capacity:
            raise ValueError(
                f"delta layer full ({self.delta_capacity} slots) — "
                f"compact() first")
        slot = self._d_occ
        self._d_keys[slot] = key
        self._d_vals[slot] = value
        self._d_search[slot] = np.asarray(
            self._met.prepare(jnp.asarray(key[None])))[0]
        self._d_ext[slot] = ext
        self._d_live[slot] = True
        self._d_occ = slot + 1
        self._loc[ext] = ("d", slot)
        self._dirty = True
        if (self._d_occ >= self.delta_graph_min
                and self._d_occ >= 2 * max(1, self._dg_n)):
            self._rebuild_delta_graph(self._d_occ)

    def _apply_delete(self, ext: int) -> None:
        kind, pos = self._loc.pop(ext)
        if kind == "d":
            self._d_live[pos] = False
            self._dirty = True
        else:
            self._tomb_ext.add(ext)
            self._tomb_version += 1

    def _rebuild_delta_graph(self, n: int) -> None:
        """(Re)build the small Vamana over delta slots [0, n).

        Occupancy-doubling schedule: each slot is included in O(log C)
        builds total, so incremental insertion stays amortized-cheap while
        delta search work drops from O(occ) brute distances to a graph
        walk + an O(occ - n) brute tail.  Dead slots stay nodes (masked at
        candidate time), mirroring the main graph's tombstone treatment.
        """
        params = self.main.params.clamped(n)
        res = vamana_lib.build_vamana(
            jnp.asarray(self._d_search[:n]), params,
            metric=self._met.kernel)
        self._dg_ids = res.g.ids[0]
        self._dg_entry = int(res.entry)
        self._dg_n = n

    # -- search -------------------------------------------------------------

    def _tomb_rows_device(self) -> jax.Array | None:
        """Tombstoned MAIN ROWS as a bucketed INVALID-padded device array
        (None when empty — the search then dispatches the healthy
        ``tombstones=False`` cached program)."""
        if not self._tomb_ext:
            return None
        ver, cached = self._tomb_cache
        if ver == self._tomb_version:
            return cached
        ext2row = {int(e): r for r, e in enumerate(self.main_ext)}
        rows = np.sort(np.fromiter(
            (ext2row[e] for e in self._tomb_ext), np.int32,
            count=len(self._tomb_ext)))
        width = graph_lib.bucket(rows.size, TOMB_BLOCK_MULT)
        padded = np.full(width, INVALID, np.int32)
        padded[:rows.size] = rows
        dev = jnp.asarray(padded)
        self._tomb_cache = (self._tomb_version, dev)
        return dev

    def _sync_delta(self) -> None:
        """Push host delta buffers to their device mirrors (lazily, once
        per mutation batch — searches between mutations pay nothing)."""
        if not self._dirty:
            return
        self._d_search_dev = jnp.asarray(self._d_search)
        self._d_live_dev = jnp.asarray(self._d_live)
        self._cat_idx = dataclasses.replace(
            self.main,
            keys=jnp.concatenate(
                [self.main.keys, jnp.asarray(self._d_keys)], axis=0),
            values=jnp.concatenate(
                [self.main.values, jnp.asarray(self._d_vals)], axis=0))
        self._cat_ext_dev = jnp.asarray(
            np.concatenate([self.main_ext, self._d_ext]))
        self._dirty = False

    def _ext_ids(self, pool_ids: jax.Array, table: jax.Array) -> jax.Array:
        return jnp.where(pool_ids == INVALID, INVALID,
                         table[jnp.maximum(pool_ids, 0)])

    def _delta_candidates(self, qb: jax.Array, row_mask: jax.Array,
                          ef: int, visited_impl: str, expand_width: int):
        """Delta-layer candidates for one query block.

        Returns (slot ids, dists, extra #dist for the block, extra hops).
        Graph mode searches the Vamana prefix then brute-forces the tail;
        the two slot ranges are disjoint, so their concatenation enters
        ``_merge_topk`` duplicate-free.  Dead slots mask to INVALID/inf
        here — the delta needs no tombstone array, its liveness bitmap IS
        the mask.
        """
        kc = min(ef, self.delta_capacity)
        brute = _delta_brute_fn(self._met.kernel, kc)
        nrows = int(jnp.sum(row_mask)) if row_mask is not None else \
            qb.shape[0]
        ids_b, dist_b, n_live_tail = brute(
            qb, self._d_search_dev, self._d_live_dev,
            jnp.int32(self._dg_n))
        n_extra = int(n_live_tail) * nrows
        hops = jnp.int32(0)
        if self._dg_n == 0:
            return ids_b, dist_b, n_extra, hops
        efd = min(ef, self._dg_n)
        res = search_lib.knn_search(
            self._dg_ids, self._d_search_dev[:self._dg_n], qb,
            efd, efd, self._dg_entry, metric=self._met.kernel,
            visited_impl=visited_impl, expand_width=expand_width,
            row_mask=row_mask)
        alive = (res.pool_ids != INVALID) & \
            self._d_live_dev[jnp.maximum(res.pool_ids, 0)]
        ids_g = jnp.where(alive, res.pool_ids, INVALID)
        dist_g = jnp.where(alive, res.pool_dist, jnp.inf)
        return (jnp.concatenate([ids_g, ids_b], axis=-1),
                jnp.concatenate([dist_g, dist_b], axis=-1),
                n_extra + int(res.n_computed), res.hops)

    def attention_batched(self, q: jax.Array, *, top_k: int, ef: int,
                          scale: float | None = None, block_size: int = 64,
                          visited_impl: str = "hash",
                          expand_width: int =
                          retrieval_lib.DEFAULT_EXPAND_WIDTH,
                          routed_shards: int | None = None,
                          shard_mask=None):
        """Batched retrieval attention over main ∪ delta − tombstones.

        Calling convention of ``retrieval.retrieval_attention_batched``
        (so ``ResilientSearcher`` dispatches here unchanged); result pool
        ids are EXTERNAL ids.  Pristine state short-circuits to the
        wrapped index's own batched path — bit-identical serving.  Else,
        per block: the main index searches with a FULL ef-wide pool (plus
        the tombstone mask at its merge fold), delta candidates fold in
        through ``_merge_topk`` (main pool wins distance ties — the
        bit-pinned rule), and only then is the pool truncated to top_k, so
        the ef − k slack refills what tombstones evict.
        """
        if self.pristine:
            out, res = retrieval_lib.retrieval_attention_batched(
                self.main, q, top_k=top_k, ef=ef, scale=scale,
                block_size=block_size, visited_impl=visited_impl,
                expand_width=expand_width, routed_shards=routed_shards,
                shard_mask=shard_mask)
            if self._ext_identity:
                return out, res
            if self._main_ext_dev is None:
                self._main_ext_dev = jnp.asarray(self.main_ext)
            return out, res._replace(
                pool_ids=self._ext_ids(res.pool_ids, self._main_ext_dev))
        B, dh = q.shape
        if B == 0:
            raise ValueError("empty query batch")
        self._sync_delta()
        tomb = self._tomb_rows_device()
        qs_all = self._met.prepare(q)
        bs = graph_lib.bucket(min(block_size, B), 16)
        pool_ids, pool_dist, n_fresh, n_comp, hop_cnt = [], [], [], [], []
        extra_dist = 0
        res = None
        for off in range(0, B, bs):
            nrows = min(bs, B - off)
            qb = jnp.zeros((bs, dh), qs_all.dtype).at[:nrows].set(
                qs_all[off:off + nrows])
            rmask = jnp.arange(bs) < nrows
            res = retrieval_lib._search_index(
                self.main, qb, ef, ef, visited_impl, expand_width,
                row_mask=rmask, routed_shards=routed_shards,
                shard_mask=shard_mask, tombstone_ids=tomb)
            pi, pd = res.pool_ids, res.pool_dist
            if self._d_occ:
                dids, ddist, n_extra, dhops = self._delta_candidates(
                    qb, rmask, ef, visited_impl, expand_width)
                cand = jnp.where(dids == INVALID, INVALID,
                                 dids + self.n_main)
                pi, pd, _ = search_lib._merge_topk(
                    pi, pd, jnp.zeros_like(pi, bool), cand, ddist)
                extra_dist += n_extra
                hop_cnt.append(dhops)
            pool_ids.append(pi[:nrows, :top_k])
            pool_dist.append(pd[:nrows, :top_k])
            n_fresh.append(res.n_fresh)
            n_comp.append(res.n_computed)
            hop_cnt.append(res.hops)
        ids = jnp.concatenate(pool_ids, axis=0)
        agg = search_lib.SearchResult(
            self._ext_ids(ids, self._cat_ext_dev),
            jnp.concatenate(pool_dist, axis=0),
            jnp.sum(jnp.stack(n_fresh)) + extra_dist,
            jnp.sum(jnp.stack(n_comp)) + extra_dist,
            jnp.max(jnp.stack(hop_cnt)), res.cache_d, res.cache_has)
        out = retrieval_lib._attend(self._cat_idx, q, ids, scale)
        return out, agg

    def knn(self, q: jax.Array, k: int, ef: int, **kw):
        """Plain k-ANNS over the mutable corpus: (ext ids, dists)."""
        _, res = self.attention_batched(q, top_k=k, ef=ef, **kw)
        return res.pool_ids, res.pool_dist

    # -- compaction ---------------------------------------------------------

    def maybe_compact(self, searcher=None) -> bool:
        """Compact when the delta is full or the tombstone fraction
        crosses ``tombstone_compact_frac``; hot-swaps into ``searcher``
        (``ResilientSearcher.swap_index``) when given.  Returns whether a
        compaction ran.  This is the off-path trigger a serving loop calls
        between requests — searches themselves never compact."""
        if (self._d_occ < self.delta_capacity
                and self.tombstone_fraction < self.tombstone_compact_frac):
            return False
        self.compact(searcher=searcher)
        return True

    def compact(self, *, searcher=None) -> None:
        """Fold delta + tombstones into a new main generation.

        Unsharded: one fused rebuild over the live vectors.  Sharded: only
        AFFECTED shards rebuild — a shard is affected iff it owns a
        tombstoned row or receives a delta vector (nearest centroid, the
        same routing statistic searches use); untouched shards keep their
        adjacency/data arrays byte-for-byte, with only their global ids
        renumbered into the compacted row space, and everything restacks
        through ``graph.assemble_sharded``.  With ``wal_dir``, the new
        generation persists snapshot-first, pointer-last (the atomic
        commit), then the old generation's files are removed — a crash
        anywhere before the pointer lands recovers the OLD generation plus
        its complete WAL.  Serving reads the old index object until
        ``searcher.swap_index`` commits the new one.
        """
        main = self.main
        live_mask = np.ones(self.n_main, bool)
        if self._tomb_ext:
            ext2row = {int(e): r for r, e in enumerate(self.main_ext)}
            for e in self._tomb_ext:
                live_mask[ext2row[e]] = False
        live_rows = np.nonzero(live_mask)[0]
        d_slots = np.nonzero(self._d_live[:self._d_occ])[0]
        keys_np = np.asarray(main.keys)
        vals_np = np.asarray(main.values)
        new_keys = np.concatenate([keys_np[live_rows],
                                   self._d_keys[d_slots]])
        new_vals = np.concatenate([vals_np[live_rows],
                                   self._d_vals[d_slots]])
        new_ext = np.concatenate([self.main_ext[live_rows],
                                  self._d_ext[d_slots]])
        n_new = new_keys.shape[0]
        if n_new < 2:
            raise ValueError(
                f"refusing to compact down to {n_new} vectors: a graph "
                f"needs at least 2 nodes")
        new_search = np.asarray(self._met.prepare(jnp.asarray(new_keys)))
        prov = dict(main.provenance or {})
        prov["build_impl"] = "fused"
        if main.shards is None:
            lids, entry = self._build(new_search)
            search_dev = jnp.asarray(new_search)
            # The new generation inherits the main index's quantization
            # mode; codes are recomputed over the compacted corpus (scale
            # shifts as rows churn — stale codes would skew distances).
            quant = (metric_lib.quantize_sq8(search_dev)
                     if main.quantize == "sq8" else None)
            new_main = retrieval_lib.RetrievalIndex(
                graph_ids=jnp.asarray(lids), keys=jnp.asarray(new_keys),
                values=jnp.asarray(new_vals),
                search_keys=search_dev, entry=int(entry),
                params=main.params, metric=main.metric, provenance=prov,
                quantize=main.quantize, quant=quant)
        else:
            new_main = self._compact_sharded(
                main, live_mask, live_rows, d_slots, new_keys, new_vals,
                new_search, prov)
        self.main = new_main
        self.n_main = n_new
        self.main_ext = np.asarray(new_ext, np.int32)
        self._ext_identity = bool(np.array_equal(
            self.main_ext, np.arange(n_new, dtype=np.int32)))
        self._loc = {int(e): ("m", r)
                     for r, e in enumerate(self.main_ext)}
        self._d_ext[:] = INVALID
        self._d_live[:] = False
        self._d_occ = 0
        self._dg_ids, self._dg_n = None, 0
        self._tomb_ext = set()
        self._tomb_version += 1
        self._dirty = True
        self._main_ext_dev = None
        # Release the OLD generation's corpus-sized device buffers now.
        # ``_dirty`` alone is not enough: a post-compact index with an
        # empty delta is *pristine*, so ``attention_batched`` short-
        # circuits to the main path and ``_sync_delta`` never runs to
        # replace these mirrors — they would pin the old keys/values/
        # search arrays on device for the life of the process.
        self._cat_idx = None
        self._cat_ext_dev = None
        self._d_search_dev = None
        self._d_live_dev = None
        self._tomb_cache = (-1, None)
        self.gen += 1
        self.compactions += 1
        if self.wal_dir is not None:
            self._persist_generation()
        if searcher is not None:
            searcher.swap_index(self)

    def _compact_sharded(self, main, live_mask, live_rows, d_slots,
                         new_keys, new_vals, new_search, prov):
        """Affected-shard rebuild + restack (see ``compact``)."""
        sg = main.shards
        S = sg.num_shards
        old2new = np.full(self.n_main, INVALID, np.int64)
        old2new[live_rows] = np.arange(live_rows.size)
        # Route each live delta vector to its nearest live centroid.
        assign: list[list[int]] = [[] for _ in range(S)]
        if d_slots.size:
            dprep = jnp.asarray(self._d_search[d_slots])
            scores = np.asarray(metric_lib.kernel_distance(
                dprep[:, None, :], sg.centroids[None, :, :],
                self._met.kernel))
            for j, s in enumerate(np.argmin(scores, axis=-1)):
                # new row of delta slot d_slots[j]
                assign[int(s)].append(live_rows.size + j)
        gids_np = np.asarray(sg.global_ids)
        counts_np = np.asarray(sg.counts)
        ids_parts, data_parts, gid_parts, entries = [], [], [], []
        for s in range(S):
            c = int(counts_np[s])
            members = gids_np[s, :c]
            keep = live_mask[members]
            new_members = old2new[members[keep]].astype(np.int32)
            adds = np.asarray(assign[s], np.int32)
            if keep.all() and adds.size == 0:
                # Untouched: graph + local vectors byte-identical, only
                # the global-id renumbering changes.
                ids_parts.append(np.asarray(sg.ids[s, :c]))
                data_parts.append(np.asarray(sg.data[s, :c]))
                gid_parts.append(new_members)
                entries.append(int(sg.entries[s]))
                continue
            rows = np.concatenate([new_members, adds])
            if rows.size == 0:
                raise ValueError(
                    f"compaction would empty shard {s}: every member is "
                    f"tombstoned and no delta vector routes there — "
                    f"repartition (build_index) instead of compacting")
            local = new_search[rows]
            lids, entry = self._build(local)
            ids_parts.append(np.asarray(lids))
            data_parts.append(local)
            gid_parts.append(rows)
            entries.append(int(entry))
        mesh = getattr(getattr(sg.ids, "sharding", None), "mesh", None)
        shards = graph_lib.assemble_sharded(
            ids_parts, data_parts, gid_parts, entries,
            centroids=np.asarray(sg.centroids), mesh=mesh)
        if main.quantize == "sq8":
            # Re-quantize over the compacted shard stack (global scale
            # shifts as rows churn); untouched shards keep their fp32
            # data byte-identical, only the side-car codes refresh.
            shards = graph_lib.quantize_sharded(
                shards, metric=self._met.kernel, mesh=mesh)
        entry = int(shards.global_ids[0][int(shards.entries[0])])
        return retrieval_lib.RetrievalIndex(
            graph_ids=None, keys=jnp.asarray(new_keys),
            values=jnp.asarray(new_vals), search_keys=None, entry=entry,
            params=main.params, metric=main.metric, shards=shards,
            provenance=prov, quantize=main.quantize)

    def _default_build(self, local):
        """Compaction build hook: fused Vamana with the main params
        (clamped to the piece being rebuilt).  Overridable via
        ``build_fn`` — benches inject cheap random graphs the way
        kernel_microbench does for its shard graphs."""
        prov = self.main.provenance or {}
        res = vamana_lib.build_vamana(
            jnp.asarray(local),
            self.main.params.clamped(int(local.shape[0])),
            seed=int(prov.get("seed", 0)),
            batch_size=int(prov.get("batch_size", 256)),
            metric=self._met.kernel)
        return res.g.ids[0], res.entry

    # -- persistence --------------------------------------------------------

    def _wal_path(self) -> str:
        return os.path.join(self.wal_dir,
                            f"{self.tag}-g{self.gen}{WAL_SUFFIX}")

    def _persist_generation(self) -> None:
        """Commit the current generation: snapshot + sidecar first, fresh
        WAL truncated, pointer JSON LAST (the atomic commit record), old
        generation removed only after the pointer lands."""
        from repro.serve import resilience as resilience_lib
        gtag = f"{self.tag}-g{self.gen}"
        resilience_lib.save_index(self.main, self.wal_dir, tag=gtag)
        ckpt_lib.atomic_write_npz(
            os.path.join(self.wal_dir, gtag + STREAM_STATE),
            {"main_ext": self.main_ext})
        # An orphaned WAL at this generation number (a compaction that
        # crashed after writing files but before committing the pointer,
        # then recovered at the old generation) must not resurface.
        wal = self._wal_path()
        if os.path.exists(wal):
            os.unlink(wal)
        ckpt_lib.atomic_write_json(
            os.path.join(self.wal_dir, self.tag + STREAM_POINTER),
            {"format": STREAM_FORMAT, "gen": self.gen, "tag": self.tag,
             "applied_seq": self._next_seq - 1,
             "next_ext": self._next_ext})
        prev = self.gen - 1
        if prev >= 0:
            ptag = f"{self.tag}-g{prev}"
            for name in (ptag + resilience_lib.SNAPSHOT_NPZ,
                         ptag + resilience_lib.SNAPSHOT_MANIFEST,
                         ptag + STREAM_STATE, ptag + WAL_SUFFIX):
                p = os.path.join(self.wal_dir, name)
                if os.path.exists(p):
                    os.unlink(p)
