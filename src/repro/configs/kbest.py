"""The paper's own system configs: recommended KBest index parameters per
evaluation dataset (paper Table 3/4), exposed like the arch configs.

    from repro.configs import kbest
    cfg = kbest.index_config("bigann_like")            # graph index
    cfg = kbest.ivf_index_config("bigann_like")        # IVF-PQ index
    cfg = kbest.sharded_index_config("bigann_like", 4) # 4-shard graph mesh

Graph presets tune the build/search pipeline of DESIGN.md §3; the IVF
presets (DESIGN.md §4) tune (nlist auto, nprobe, pq_m) to reach
recall@10 >= 0.90 on the 50k synthetic analogues with full-queue re-rank.
The sharded presets (DESIGN.md §12) stamp n_shards onto the same tuned
configs — build them with repro.core.sharded.ShardedKBest.
"""
import dataclasses

from repro.core.types import (BuildConfig, IVFConfig, IndexConfig,
                              QuantConfig, SearchConfig)

ARCH_ID = "kbest"
FAMILY = "anns"
SHAPES = ("glove_like", "deep_like", "t2i_like", "bigann_like")

# (dim, metric, build, search) tuned on the synthetic analogues to reach
# recall@10 >= 0.95 (benchmarks/qps_recall.py). deep_like is sized for its
# 1M-row deployment (chip_smoke.py): recall@10 at L=64 fell from the 50k
# figure to 0.857 at 100k rows (CPU, 300 queries), and L=192 with
# patience 32 measured 0.779 at 1M rows (one TPU v5e, 770 queries), so the
# queue is doubled again.
_CONFIGS = {
    "glove_like": dict(
        dim=100, metric="ip",
        build=BuildConfig(M=32, knn_k=48, select_rule="alpha", alpha=1.2,
                          search_passes=2, refine_iters=2, refine_cands=96,
                          reorder="mst"),
        search=SearchConfig(L=128, k=10, early_term=True, et_patience=32)),
    "deep_like": dict(
        dim=96, metric="ip",
        build=BuildConfig(M=24, knn_k=32, select_rule="alpha", alpha=1.2,
                          search_passes=1, refine_iters=1, refine_cands=64,
                          reorder="mst"),
        search=SearchConfig(L=384, k=10, early_term=True, et_patience=48)),
    "t2i_like": dict(
        dim=200, metric="ip",
        build=BuildConfig(M=32, knn_k=48, select_rule="alpha", alpha=1.2,
                          search_passes=2, refine_iters=1, refine_cands=96,
                          reorder="mst"),
        search=SearchConfig(L=128, k=10, early_term=True, et_patience=32)),
    "bigann_like": dict(
        dim=128, metric="l2",
        build=BuildConfig(M=32, knn_k=48, select_rule="alpha", alpha=1.2,
                          search_passes=2, refine_iters=1, refine_cands=96,
                          reorder="mst"),
        search=SearchConfig(L=192, k=10, early_term=True, et_patience=48)),
}


# IVF-PQ presets: pq_m must divide dim; nprobe/L tuned for the re-ranked
# pipeline (candidate recall == final recall with rerank=0 => full queue).
# deep_like probes for its 1M-row deployment: with nlist = sqrt(n), a fixed
# nprobe covers a shrinking share of the lists as n grows, and nprobe=24
# measured recall@10 0.876 at 50k but 0.798 at 400k; nprobe=96 with L=256
# measured 0.978 at 400k (CPU, 300 queries).
_IVF_CONFIGS = {
    "glove_like": dict(dim=100, metric="ip", pq_m=20, nprobe=32, L=192),
    "deep_like": dict(dim=96, metric="ip", pq_m=16, nprobe=96, L=256),
    "t2i_like": dict(dim=200, metric="ip", pq_m=20, nprobe=32, L=192),
    "bigann_like": dict(dim=128, metric="l2", pq_m=16, nprobe=32, L=192),
}


# pq4 presets (DESIGN.md §13): 4-bit codes are coarser per subspace, so the
# presets spend (some of) the halved bytes on more subspaces and widen the
# re-ranked candidate queue / probe count to hold the recall floor.
_IVF_PQ4_CONFIGS = {
    "glove_like": dict(dim=100, metric="ip", pq_m=20, nprobe=48, L=256),
    "deep_like": dict(dim=96, metric="ip", pq_m=32, nprobe=32, L=192),
    "t2i_like": dict(dim=200, metric="ip", pq_m=40, nprobe=48, L=256),
    "bigann_like": dict(dim=128, metric="l2", pq_m=32, nprobe=48, L=384),
}


# bin presets (DESIGN.md §14): 1-bit Hamming first pass needs a wider
# queue and a deep exact rescore — (L, rescore_factor) on the graph side,
# (nprobe, ivf_L, ivf_rescore_factor) on the IVF side — to hold the 0.90
# recall floor at 32x-smaller-than-f32 codes. The IVF flat scan keeps no
# traversal queue, so its overfetch must be much deeper than the graph's
# (the graph's Hamming-ordered frontier already concentrates true
# neighbours near the top): deep_like at 50k measures 0.92 at
# nprobe=96/rf=64 but only 0.85 at nprobe=64/rf=32.
_BIN_CONFIGS = {
    "glove_like": dict(L=320, rescore_factor=32,
                       nprobe=96, ivf_L=768, ivf_rescore_factor=64),
    "deep_like": dict(L=320, rescore_factor=32,
                      nprobe=96, ivf_L=768, ivf_rescore_factor=64),
    "t2i_like": dict(L=320, rescore_factor=32,
                     nprobe=96, ivf_L=768, ivf_rescore_factor=64),
    "bigann_like": dict(L=384, rescore_factor=32,
                        nprobe=96, ivf_L=768, ivf_rescore_factor=64),
}


def index_config(dataset: str) -> IndexConfig:
    return IndexConfig(**_CONFIGS[dataset])


def bin_index_config(dataset: str) -> IndexConfig:
    """Graph preset with the 1-bit sign codec (DESIGN.md §14): Hamming
    traversal over u32-packed codes + exact rescore of the
    rescore_factor*k overfetch."""
    cfg = index_config(dataset)
    b = _BIN_CONFIGS[dataset]
    return dataclasses.replace(
        cfg,
        quant=QuantConfig(kind="bin"),
        search=dataclasses.replace(cfg.search, L=b["L"],
                                   rescore_factor=b["rescore_factor"]))


def sq_index_config(dataset: str) -> IndexConfig:
    """Graph preset with the int8 scalar quantizer (DESIGN.md §13): per-dim
    affine u8 codes traversed directly (gather+dequant fused in the kernel
    path), exact re-rank of the default 4*k overfetch. SQ is nearly
    recall-transparent at 4x-smaller codes, so the tuned graph knobs carry
    over unchanged."""
    return dataclasses.replace(index_config(dataset),
                               quant=QuantConfig(kind="sq"))


def ivf_bin_index_config(dataset: str) -> IndexConfig:
    """IVF preset with the 1-bit sign codec (DESIGN.md §14): XOR+popcount
    list scans (no LUT stage) + exact rescore. The deep_like preset is the
    50k acceptance config of tests/test_bin."""
    c = _IVF_CONFIGS[dataset]
    b = _BIN_CONFIGS[dataset]
    return IndexConfig(
        dim=c["dim"], metric=c["metric"], index_type="ivf",
        ivf=IVFConfig(nlist=0, kmeans_iters=10),
        quant=QuantConfig(kind="bin"),
        search=SearchConfig(L=b["ivf_L"], k=10, nprobe=b["nprobe"],
                            rescore_factor=b["ivf_rescore_factor"]))


def ivf_index_config(dataset: str) -> IndexConfig:
    c = _IVF_CONFIGS[dataset]
    return IndexConfig(
        dim=c["dim"], metric=c["metric"], index_type="ivf",
        ivf=IVFConfig(nlist=0, kmeans_iters=10),
        quant=QuantConfig(kind="pq", pq_m=c["pq_m"], kmeans_iters=8),
        search=SearchConfig(L=c["L"], k=10, nprobe=c["nprobe"]))


def ivf_pq4_index_config(dataset: str) -> IndexConfig:
    """4-bit fast-scan IVF presets (half the code bytes of ivf_index_config
    at equal m; these double m where dim allows, trading bytes for recall).
    The bigann_like preset is the 50k acceptance config of tests/test_pq4."""
    c = _IVF_PQ4_CONFIGS[dataset]
    return IndexConfig(
        dim=c["dim"], metric=c["metric"], index_type="ivf",
        ivf=IVFConfig(nlist=0, kmeans_iters=10),
        quant=QuantConfig(kind="pq4", pq_m=c["pq_m"], kmeans_iters=10),
        search=SearchConfig(L=c["L"], k=10, nprobe=c["nprobe"]))


# Beam presets (DESIGN.md §2): W per dataset, tuned so the beam cuts
# lockstep iterations ~W x at equal recall on the 50k analogues
# (benchmarks/traverse.py measures the trade; W=1 == classic best-first).
_BEAM_W = {"glove_like": 4, "deep_like": 4, "t2i_like": 4, "bigann_like": 4}


def beam_index_config(dataset: str, beam_width: int = 0) -> IndexConfig:
    """Graph preset searched with beam-parallel traversal (DESIGN.md §2):
    top-W unvisited candidates expand per lockstep iteration, feeding the
    fused gather+distance+merge step W*M candidates at once. beam_width=0
    takes the per-dataset tuned width; ET patience is per-expansion (Eq. 3
    in beam order), so the preset patience needs no rescaling."""
    cfg = index_config(dataset)
    w = beam_width if beam_width > 0 else _BEAM_W[dataset]
    return dataclasses.replace(
        cfg, search=dataclasses.replace(cfg.search, beam_width=w))


def sharded_index_config(dataset: str, n_shards: int = 2) -> IndexConfig:
    """Graph preset on an n_shards mesh (DESIGN.md §12). Per-shard knobs
    are the single-shard tuning: each shard runs the full traversal at the
    preset L, so the merged recall only goes up (scaling.py measures the
    cost side)."""
    return dataclasses.replace(index_config(dataset), n_shards=n_shards)


def sharded_ivf_index_config(dataset: str, n_shards: int = 2) -> IndexConfig:
    """IVF-PQ preset on an n_shards mesh: every shard trains its own coarse
    centroids (nlist=0 => sqrt(n_shard)) and probes nprobe of them, so the
    total scanned lists grow with the mesh — recall floor holds per shard."""
    return dataclasses.replace(ivf_index_config(dataset), n_shards=n_shards)


def sharded_ivf_pq4_index_config(dataset: str,
                                 n_shards: int = 2) -> IndexConfig:
    """4-bit fast-scan IVF preset on an n_shards mesh (DESIGN.md §12+§13:
    quantized shard-local scan, shard-local exact re-rank, global merge)."""
    return dataclasses.replace(ivf_pq4_index_config(dataset),
                               n_shards=n_shards)


def sharded_bin_index_config(dataset: str, n_shards: int = 2) -> IndexConfig:
    """1-bit sign-codec graph preset on an n_shards mesh (DESIGN.md
    §12+§14: shard-local Hamming traversal + exact rescore, global merge)."""
    return dataclasses.replace(bin_index_config(dataset), n_shards=n_shards)


def sharded_smoke_config(n_shards: int = 2) -> IndexConfig:
    """Tiny sharded-graph config for CI-speed mesh tests."""
    return dataclasses.replace(smoke_config(), n_shards=n_shards)


def full_config(dataset: str = "bigann_like") -> IndexConfig:
    return index_config(dataset)


def smoke_config() -> IndexConfig:
    return IndexConfig(
        dim=32, metric="l2",
        build=BuildConfig(M=8, knn_k=12, refine_iters=1, refine_cands=24,
                          reorder="mst"),
        search=SearchConfig(L=16, k=5))


def tune_grid(index_type: str) -> dict:
    """Search-knob grid core/tune.py::tune_config sweeps (DESIGN.md §16).
    Quant kinds are NOT enumerated here — the tuner takes them from the
    registry (types.QUANT_KINDS / quantize.IVF_QUANT_KINDS), so a new
    kind lands in the tuner automatically. rescore_factor only fans out
    for kind="bin" (the only kind that reads it)."""
    if index_type == "ivf":
        return {"L": (32, 64, 128, 256), "nprobe": (4, 8, 16, 32, 64),
                "rescore_factor": (8, 32)}
    return {"L": (32, 64, 128, 256), "beam_width": (1, 4),
            "rescore_factor": (8, 32)}


def degrade_ladder(cfg: IndexConfig, n_rungs: int = 4) -> tuple:
    """Pre-tuned shed valve for the serving tier (DESIGN.md §17): rung 0 is
    the preset's own SearchConfig; each further rung halves the dominant
    accuracy/cost knobs — L and rescore_factor always, nprobe on the IVF
    path — subject to the k <= L / beam_width <= L invariants. Candidates
    that don't STRICTLY lower the predicted per-query cost are dropped
    (e.g. halving L below the quantized wide-queue floor), so the ladder is
    monotone cost-decreasing by construction; every rung is a valid
    standalone SearchConfig (both pinned by tests/test_degrade.py)."""
    from repro.analysis.cost import predict_service_s
    s = cfg.search
    ladder = [s]
    last_cost = predict_service_s(cfg, s)
    while len(ladder) < n_rungs:
        cand = dataclasses.replace(
            s,
            L=max(s.k, s.beam_width, s.L // 2),
            nprobe=max(1, s.nprobe // 2),
            rescore_factor=max(1, s.rescore_factor // 2))
        if cand == s:
            break                        # every knob is at its floor
        s = cand
        c = predict_service_s(cfg, s)
        if c < last_cost * 0.999:
            ladder.append(s)
            last_cost = c
    return tuple(ladder)


def ivf_smoke_config() -> IndexConfig:
    return IndexConfig(
        dim=32, metric="l2", index_type="ivf",
        ivf=IVFConfig(nlist=8, kmeans_iters=4, list_pad=8),
        quant=QuantConfig(kind="pq", pq_m=8, kmeans_iters=3),
        search=SearchConfig(L=16, k=5, nprobe=4))
