"""ShardedKBest — shard-per-device composition of KBest indexes (DESIGN.md §12).

The paper's KBest is single-node; at pod scale the standard architecture
(the one Milvus deploys KBest into, and the one KScaNN scales to billions
of vectors) is shard-per-device + merge:

  * the corpus is split into P contiguous row ranges ("shards");
  * each shard is built as an INDEPENDENT single-shard KBest — its own
    proximity graph + medoid entry points (graph family) or its own coarse
    centroids + inverted lists (IVF family), and its own PQ/SQ codebooks —
    so no cross-shard edges or lists exist;
  * a query runs the full shard-local pipeline on every shard, including
    the quantized first pass (pq8 / pq4 / sq ADC, or the bin codec's
    XOR+popcount Hamming + rescore — DESIGN.md §14) and the SHARD-LOCAL
    exact re-rank, then the per-shard exact top-k are merged into the
    global top-k (one O(P·k) reduction over exact distances).

Recall of a sharded index is >= the single-shard index at equal per-shard
L, because each shard runs its own full traversal (more total distance
evaluations buy the recall; the QPS/recall trade is measured in
benchmarks/scaling.py and asserted in tests/test_sharded.py). With P = 1
the composition is bit-identical to plain KBest: the merge of one shard's
sorted top-k is the identity.

The SearchConfig is applied per shard verbatim — a beam_width W
(DESIGN.md §2) means every shard's traversal expands W candidates per
lockstep iteration, so the merged `iters` (critical path) drops ~W× across
the whole mesh and P=1 beam results stay bit-identical to plain KBest.

Stats-merge semantics (`with_stats=True`): per-shard `n_hops` and `n_dist`
are SUMMED per query (total work across the mesh, keeping the
dists-per-query telemetry in the same cross-family units as DESIGN.md §4);
`early_terminated` is the logical AND over shards (a merged lane counts as
early-terminated only when every shard's traversal fired Eq. 3);
`iters` is the max over shards (critical-path lockstep iterations). All
reduce to the single-index stats at P = 1.

Ids returned to the caller are GLOBAL row ids into the original add()
matrix: shard s translates its local results by `offsets[s]` (each shard's
internal reorder permutation is already undone inside KBest._search_impl).

Execution: the Python loop over shards unrolls under one jit trace (the
serving engine compiles it as a single XLA program per shape bucket — the
engine's cache key carries `IndexConfig.n_shards` as the mesh shape). For
a physical device mesh, `build_sharded_search`/`make_sharded_arrays` below
keep the `jax.shard_map` lowering of the full-precision graph path, where
the same shard-local-search + all-gather + top-k merge runs one shard per
device ((16, 16) and (2, 16, 16) production meshes in the dry-run, the
1-device CPU mesh in tests).

Persistence: `save(path)` writes each shard through `KBest.save` as
`<path>.shard<s>[.npz/.json]` plus ONE `<path>.sharded.json` sidecar
(n_shards, row offsets, full config); `load` reconstructs every shard.
"""
from __future__ import annotations

import dataclasses
import functools
import json
from pathlib import Path
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import index as index_mod
from repro.core import persist
from repro.core import search as search_mod
from repro.core.index import (KBest, _config_from_dict, _config_to_dict,
                              mask_padded_lanes, prep_queries,
                              resolve_search_cfg)
from repro.core.types import IndexConfig, SearchConfig


def shard_bounds(n: int, n_shards: int) -> np.ndarray:
    """(P+1,) row offsets of the contiguous shard split.

    The first n % P shards take one extra row, so ANY n >= P shards without
    padding or truncation — uneven corpora are first-class (the device-mesh
    layout path, which does need equal shards, pads instead: see
    make_sharded_arrays)."""
    assert n >= n_shards >= 1, (n, n_shards)
    base, rem = divmod(n, n_shards)
    sizes = np.full(n_shards, base, np.int64)
    sizes[:rem] += 1
    return np.concatenate([[0], np.cumsum(sizes)])


def merge_stats(per_shard: Sequence[search_mod.SearchStats]
                ) -> search_mod.SearchStats:
    """Fold per-shard stats into one merged SearchStats (semantics in the
    module docstring; identity for a single shard)."""
    return search_mod.SearchStats(
        n_hops=functools.reduce(jnp.add, [s.n_hops for s in per_shard]),
        n_dist=functools.reduce(jnp.add, [s.n_dist for s in per_shard]),
        early_terminated=functools.reduce(
            jnp.logical_and, [s.early_terminated for s in per_shard]),
        iters=functools.reduce(jnp.maximum, [s.iters for s in per_shard]),
    )


class ShardedKBest:
    """KBest's API surface over a mesh of independent per-shard indexes.

    Mirrors the facade of core/index.py (add / search / search_padded /
    save / load, plus the `_resolve_cfg` hook the serving engine keys on),
    so `SearchEngine` serves it unchanged.
    """

    def __init__(self, config: IndexConfig, n_shards: Optional[int] = None):
        if n_shards is not None and n_shards != config.n_shards:
            config = dataclasses.replace(config, n_shards=n_shards)
        self.config = config
        self.shards: List[KBest] = []
        self.offsets: Optional[np.ndarray] = None   # (P+1,) global row offsets

    # ---------------------------------------------------------- properties
    @property
    def n_shards(self) -> int:
        return self.config.n_shards

    @property
    def mesh_shape(self) -> Tuple[int, ...]:
        """Flat "shards" view of the mesh (the engine cache-key component)."""
        return (self.config.n_shards,)

    @property
    def db(self) -> Optional[jnp.ndarray]:
        """Shard 0's vectors — non-None iff built (the duck-type handle the
        serving engine uses for the built-index assert and query dim)."""
        return self.shards[0].db if self.shards else None

    @property
    def n_total(self) -> int:
        return int(self.offsets[-1]) if self.offsets is not None else 0

    # ------------------------------------------------------------------ add
    def add(self, x: np.ndarray) -> "ShardedKBest":
        """Split rows into n_shards contiguous ranges and build each as an
        independent single-shard KBest (own entry points / centroids /
        codebooks)."""
        x = np.asarray(x, dtype=np.float32)
        assert x.ndim == 2 and x.shape[1] == self.config.dim, x.shape
        self.offsets = shard_bounds(x.shape[0], self.config.n_shards)
        shard_cfg = dataclasses.replace(self.config, n_shards=1)
        self.shards = [
            KBest(shard_cfg).add(x[self.offsets[s]:self.offsets[s + 1]])
            for s in range(self.config.n_shards)]
        return self

    # --------------------------------------------------------------- search
    def search(self, queries: np.ndarray, k: Optional[int] = None,
               search_cfg: Optional[SearchConfig] = None,
               with_stats: bool = False):
        """Global top-k over every shard. Same signature/returns as
        KBest.search; ids are global row ids of the add() matrix."""
        assert self.shards, "call add() first"
        scfg = self._resolve_cfg(k, search_cfg)
        dists, ids, stats = self._search_impl(
            prep_queries(self.config, queries), scfg, valid_mask=None,
            with_stats=with_stats)
        if with_stats:
            return dists, ids, stats
        return dists, ids

    def search_padded(self, queries: np.ndarray, valid_mask: np.ndarray,
                      k: Optional[int] = None,
                      search_cfg: Optional[SearchConfig] = None,
                      with_stats: bool = False):
        """Shape-stable padded-batch search (the serving entry point) —
        KBest.search_padded semantics over the sharded mesh: padded lanes
        start inactive in EVERY shard's traversal and come back as
        (+inf, -1) with zeroed merged stats."""
        assert self.shards, "call add() first"
        scfg = self._resolve_cfg(k, search_cfg)
        vm = jnp.asarray(valid_mask, dtype=bool)
        dists, ids, stats = self._search_impl(
            prep_queries(self.config, queries), scfg, valid_mask=vm,
            with_stats=with_stats)
        dists, ids, stats = mask_padded_lanes(vm, dists, ids, stats)
        if with_stats:
            return dists, ids, stats
        return dists, ids

    def _resolve_cfg(self, k: Optional[int],
                     search_cfg: Optional[SearchConfig]) -> SearchConfig:
        return resolve_search_cfg(self.config, k, search_cfg)

    def _search_impl(self, q: jnp.ndarray, scfg: SearchConfig,
                     valid_mask: Optional[jnp.ndarray], with_stats: bool):
        """Shard-local searches (quantized first pass + shard-local exact
        re-rank, all inside KBest._search_impl) -> global-id translation ->
        cross-shard exact top-k merge. Pure jax ops given concrete configs,
        so the serving engine traces the whole mesh as one program."""
        k = scfg.k
        per_d, per_i, per_s = [], [], []
        for s, shard in enumerate(self.shards):
            d, i, st = shard._search_impl(
                q, scfg, valid_mask=valid_mask, with_stats=with_stats)
            off = int(self.offsets[s])
            per_d.append(d)
            per_i.append(jnp.where(i >= 0, i + off, -1))
            per_s.append(st)
        if len(self.shards) == 1:
            # single shard: the merge is the identity — skip the top-k so
            # P=1 is bit-identical to KBest by construction
            return per_d[0], per_i[0], (merge_stats(per_s)
                                        if with_stats else None)
        all_d = jnp.concatenate(per_d, axis=1)          # (Q, P*k)
        all_i = jnp.concatenate(per_i, axis=1)
        neg, pos = jax.lax.top_k(-all_d, k)
        dists = -neg
        ids = jnp.take_along_axis(all_i, pos, axis=1)
        return dists, ids, (merge_stats(per_s) if with_stats else None)

    # ------------------------------------------------------------ save/load
    @staticmethod
    def _shard_path(path: str, s: int) -> str:
        return f"{path}.shard{s}"

    def save(self, path: str) -> None:
        """Per-shard artifacts (KBest.save each, atomic + checksummed) with
        the `.sharded.json` manifest written LAST as the commit point
        (DESIGN.md §17). The manifest embeds a crc32 of every shard's
        sidecar bytes, so a crash that leaves new shards under an old
        manifest (or vice versa) is a detectable partial save, not a
        loadable mixed-generation mesh."""
        assert self.shards, "call add() first"
        p = Path(path)
        p.parent.mkdir(parents=True, exist_ok=True)
        for s, shard in enumerate(self.shards):
            shard.save(self._shard_path(path, s), _label=f"shard{s}")
        shard_meta_crc = {
            str(s): persist.file_crc32(
                index_mod._meta_path(Path(self._shard_path(path, s))))
            for s in range(len(self.shards))}
        meta = {"n_shards": self.config.n_shards,
                "offsets": np.asarray(self.offsets).tolist(),
                "config": _config_to_dict(self.config),
                "format": 2,
                "shard_meta_crc": shard_meta_crc}
        persist.atomic_write(Path(str(p) + ".sharded.json"),
                             json.dumps(meta).encode(), "manifest")

    @classmethod
    def load(cls, path: str) -> "ShardedKBest":
        """Manifest-first load: a manifest whose per-shard sidecar crc32s
        disagree with the shard files on disk means the save that wrote
        them never committed — raise persist.IndexCorruptError rather than
        assembling shards from different save generations."""
        mp = Path(str(path) + ".sharded.json")
        try:
            meta = json.loads(mp.read_text())
        except FileNotFoundError:
            raise
        except Exception as e:
            raise persist.IndexCorruptError(
                f"unreadable sharded manifest at {mp}: {e!r}") from e
        crcs = meta.get("shard_meta_crc")   # absent on pre-§17 manifests
        if crcs is not None:
            for s in range(meta["n_shards"]):
                sp = index_mod._meta_path(Path(cls._shard_path(path, s)))
                try:
                    got = persist.file_crc32(sp)
                except FileNotFoundError as e:
                    raise persist.IndexCorruptError(
                        f"manifest names shard {s} but its sidecar {sp} "
                        f"is missing (partial sharded save)") from e
                if got != int(crcs[str(s)]):
                    raise persist.IndexCorruptError(
                        f"shard {s} sidecar {sp} does not match the "
                        f"manifest (crc32 {got} != {crcs[str(s)]}) — "
                        f"partial sharded save")
        cfg = _config_from_dict(meta["config"])
        idx = cls(cfg, n_shards=meta["n_shards"])
        idx.offsets = np.asarray(meta["offsets"], dtype=np.int64)
        idx.shards = [KBest.load(idx._shard_path(path, s))
                      for s in range(meta["n_shards"])]
        return idx


# A pytree like KBest (core/index.py): the shards' arrays are the leaves,
# so the serving engine hands the whole mesh to one jitted program as an
# argument; config and row offsets are static metadata.
jax.tree_util.register_pytree_node(
    ShardedKBest,
    lambda idx: (tuple(idx.shards),
                 (idx.config, tuple(int(o) for o in idx.offsets))),
    lambda aux, shards: _sharded_unflatten(aux, shards))


def _sharded_unflatten(aux, shards) -> ShardedKBest:
    idx = ShardedKBest.__new__(ShardedKBest)
    idx.config = aux[0]
    idx.offsets = np.asarray(aux[1], np.int64)
    idx.shards = list(shards)
    return idx


# --------------------------------------------------------------------------
# Device-mesh lowering of the sharded full-precision graph path (absorbed
# from the old core/distributed.py). ShardedKBest above is the subsystem —
# device-count agnostic, quantization-aware, engine-servable; this
# shard_map path is the physical-mesh execution shape the dry-run lowers
# for the (16, 16) / (2, 16, 16) production meshes, and shares the same
# local-search + all-gather + global-top-k merge algebra.
# --------------------------------------------------------------------------

def mesh_size(mesh: Mesh) -> int:
    out = 1
    for a in mesh.axis_names:
        out *= mesh.shape[a]
    return out


def build_sharded_search(mesh: Mesh, cfg: SearchConfig, metric: str,
                         n_local: int):
    """Returns a jit'd fn(db, graph, entries, queries) -> (dists, ids).

    db:      (P*n_local, d) row-sharded over the flattened mesh
    graph:   (P*n_local, M) sharded likewise, *local* ids in [0, n_local)
    entries: (P,) i32 per-shard entry points (local ids)
    queries: (Q, d) replicated
    Output:  (Q, k) replicated global top-k; ids are GLOBAL row ids.
    """
    axes = tuple(mesh.axis_names)
    row_spec = P(axes)           # dim0 sharded over every axis, flattened
    rep = P()
    p_tot = mesh_size(mesh)

    def local_search(db_l, graph_l, entry_l, queries):
        dist_fn = search_mod.make_dist_fn(metric, cfg.dist_impl)
        dists, ids, _ = search_mod.search(
            graph_l, queries, entry_l, db_l, dist_fn=dist_fn, cfg=cfg,
            n_total=n_local)
        # translate local -> global ids using this device's linear index
        idx = jnp.int32(0)
        for a in axes:
            idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
        gids = jnp.where(ids >= 0, ids + idx * n_local, -1)
        # gather every shard's candidates and reduce to a global top-k
        all_d = jax.lax.all_gather(dists, axes)   # (P, Q, k)
        all_i = jax.lax.all_gather(gids, axes)
        Q, k = dists.shape
        all_d = all_d.reshape(p_tot, Q, k).transpose(1, 0, 2).reshape(Q, p_tot * k)
        all_i = all_i.reshape(p_tot, Q, k).transpose(1, 0, 2).reshape(Q, p_tot * k)
        neg, pos = jax.lax.top_k(-all_d, k)
        return -neg, jnp.take_along_axis(all_i, pos, axis=1)

    fn = jax.shard_map(
        local_search, mesh=mesh,
        in_specs=(row_spec, row_spec, row_spec, rep),
        out_specs=(rep, rep),
        check_vma=False,
    )
    return jax.jit(fn)


def pad_to_shard_boundary(db: np.ndarray, graph: np.ndarray, n_shards: int
                          ) -> Tuple[np.ndarray, np.ndarray, int]:
    """Pad (db, graph) rows up to n_local * P with masked sentinel rows.

    LAYOUT CONTRACT: the device-mesh path owns equal blocks — shard s is
    rows [s*n_local, (s+1)*n_local) with n_local = ceil(n / P) — so an
    uneven corpus is only representable as "every shard full except the
    LAST, which is tail-short". Appending sentinels at the global end
    completes exactly that layout; data split any other way (e.g.
    ShardedKBest's shard_bounds puts the remainder on the FIRST shards)
    must be re-laid-out into n_local blocks before calling this, or rows
    past the first short shard land on the wrong device.

    The sentinels are a zero vector with an all(-1) (edgeless) graph row.
    They are unreachable by construction: a caller's per-shard graph only
    references REAL local ids and the per-shard entry points must too, so
    a sentinel can never be seeded, expanded, or surface in the merged
    top-k. Returns (db_padded, graph_padded, n_local)."""
    db = np.asarray(db)
    graph = np.asarray(graph)
    n = db.shape[0]
    assert graph.shape[0] == n, (db.shape, graph.shape)
    n_local = -(-n // n_shards)
    pad = n_local * n_shards - n
    if pad:
        db = np.concatenate(
            [db, np.zeros((pad, db.shape[1]), db.dtype)], axis=0)
        graph = np.concatenate(
            [graph, np.full((pad, graph.shape[1]), -1, graph.dtype)], axis=0)
    return db, graph, n_local


def make_sharded_arrays(mesh: Mesh, db, graph, entries, queries):
    """device_put with the canonical shardings used by build_sharded_search.

    Uneven corpora (n % P != 0) are padded to the shard boundary with
    masked sentinel rows (pad_to_shard_boundary, whose tail-short LAYOUT
    CONTRACT applies) BEFORE placement — the old behavior handed jax a
    non-divisible dim 0, which either errored or misaligned every shard
    past the first remainder row. The real-row round-trip assert is a
    cheap sanity check that the logical array survived placement intact;
    it cannot detect a caller violating the layout contract (placement
    never reorders logical rows)."""
    axes = tuple(mesh.axis_names)
    p_tot = mesh_size(mesh)
    db = np.asarray(db)
    graph = np.asarray(graph)
    entries = np.asarray(entries)
    assert entries.shape[0] == p_tot, \
        f"need one entry point per shard: {entries.shape[0]} != {p_tot}"
    n = db.shape[0]
    db_p, graph_p, _ = pad_to_shard_boundary(db, graph, p_tot)
    row = NamedSharding(mesh, P(axes))
    rep = NamedSharding(mesh, P())
    out = (jax.device_put(db_p, row), jax.device_put(graph_p, row),
           jax.device_put(entries, row), jax.device_put(queries, rep))
    assert np.array_equal(np.asarray(out[0])[:n], db), "db round-trip"
    assert np.array_equal(np.asarray(out[1])[:n], graph), "graph round-trip"
    return out
