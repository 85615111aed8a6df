"""RecSys architectures: deepfm, fm, bst, bert4rec.

Common substrate (kernel_taxonomy §RecSys, DESIGN.md):
  * huge sparse embedding tables — a stacked (F, V, D) per-field table,
    looked up via jnp.take; multi-hot bags via layers.common.embedding_bag
    (take + segment/masked reduce: JAX has no native EmbeddingBag).
  * feature interaction: FM sum-square trick (O(F*D), Rendle ICDM'10),
    self-attention over behavior sequences (BST), bidirectional encoder
    (BERT4Rec).
  * retrieval_cand serving: score ONE query vector against 10^6 candidate
    item embeddings. This is exactly the paper's workload — the path runs
    either the batch_dist MXU kernel (exact, batched-dot) or a prebuilt
    KBest graph index (sub-linear ANN). See serve_retrieval().

Shapes (assigned): train_batch 65536 / serve_p99 512 / serve_bulk 262144 /
retrieval_cand 1 x 1e6.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.layers import common as L


@dataclasses.dataclass(frozen=True)
class RecsysConfig:
    name: str
    kind: str                    # "deepfm" | "fm" | "bst" | "bert4rec"
    n_sparse: int = 39           # categorical fields (deepfm / fm)
    vocab_per_field: int = 100_000
    embed_dim: int = 10
    mlp_dims: Tuple[int, ...] = (400, 400, 400)
    # sequence models
    n_items: int = 1_000_000     # item vocabulary (bst / bert4rec / retrieval)
    seq_len: int = 200
    n_blocks: int = 2
    n_heads: int = 2
    d_model: int = 64            # bert4rec embed_dim / bst transformer dim
    dtype: str = "float32"
    unroll_blocks: bool = False  # cost-analysis mode (see launch/dryrun)
    masked_positions: int = 0    # bert4rec (hillclimb D): compute softmax
                                 # logits ONLY at <=P masked positions per
                                 # row instead of all S x V — kills the
                                 # (B, S, V) temp blow-up at V=10^6

    @property
    def param_dtype(self):
        return jnp.bfloat16 if self.dtype == "bfloat16" else jnp.float32


# ---------------------------------------------------------------- params ---
def init_params(cfg: RecsysConfig, key) -> dict:
    dt = cfg.param_dtype
    ks = iter(jax.random.split(key, 16 + 4 * cfg.n_blocks))

    def dense(shape, scale=None):
        return L.dense_init(next(ks), shape, scale=scale, dtype=dt)

    if cfg.kind in ("deepfm", "fm"):
        F, V, D = cfg.n_sparse, cfg.vocab_per_field, cfg.embed_dim
        p = {
            "tables": dense((F, V, D), scale=0.01),
            "linear": dense((F, V), scale=0.01),     # per-field scalar weights
            "bias": jnp.zeros((), jnp.float32),
        }
        if cfg.kind == "deepfm":
            dims = (F * D,) + tuple(cfg.mlp_dims) + (1,)
            p["mlp"] = [
                {"w": dense((dims[i], dims[i + 1])),
                 "b": jnp.zeros((dims[i + 1],), dt)}
                for i in range(len(dims) - 1)
            ]
        return p

    if cfg.kind == "bst":
        Dm = cfg.d_model
        p = {
            "item_emb": dense((cfg.n_items, Dm), scale=0.02),
            "pos_emb": dense((cfg.seq_len + 1, Dm), scale=0.02),
            "blocks": _init_blocks(cfg, ks, Dm),
            "mlp": [],
        }
        dims = ((cfg.seq_len + 1) * Dm,) + tuple(cfg.mlp_dims) + (1,)
        p["mlp"] = [{"w": dense((dims[i], dims[i + 1])),
                     "b": jnp.zeros((dims[i + 1],), dt)}
                    for i in range(len(dims) - 1)]
        return p

    if cfg.kind == "bert4rec":
        Dm = cfg.d_model
        return {
            "item_emb": dense((cfg.n_items, Dm), scale=0.02),
            "pos_emb": dense((cfg.seq_len, Dm), scale=0.02),
            "blocks": _init_blocks(cfg, ks, Dm),
            "ln_f": jnp.zeros((Dm,), jnp.float32),
        }
    raise ValueError(cfg.kind)


def _init_blocks(cfg, ks, Dm):
    import jax
    blocks = []
    for _ in range(cfg.n_blocks):
        blocks.append({
            "ln1": jnp.zeros((Dm,), jnp.float32),
            "ln2": jnp.zeros((Dm,), jnp.float32),
            "wq": L.dense_init(next(ks), (Dm, Dm), dtype=cfg.param_dtype),
            "wk": L.dense_init(next(ks), (Dm, Dm), dtype=cfg.param_dtype),
            "wv": L.dense_init(next(ks), (Dm, Dm), dtype=cfg.param_dtype),
            "wo": L.dense_init(next(ks), (Dm, Dm), dtype=cfg.param_dtype),
            "w_in": L.dense_init(next(ks), (Dm, 4 * Dm), dtype=cfg.param_dtype),
            "w_out": L.dense_init(next(ks), (4 * Dm, Dm), dtype=cfg.param_dtype),
        })
    return jax.tree.map(lambda *xs: jnp.stack(xs), *blocks)


# -------------------------------------------------------------- encoders ---
def _mlp_head(mlp, x, dt):
    h = x
    for i, lyr in enumerate(mlp):
        h = h @ lyr["w"] + lyr["b"]
        if i < len(mlp) - 1:
            h = jax.nn.relu(h)
    return h


def _encoder(blocks, x, cfg, causal: bool):
    """Tiny pre-LN transformer encoder via scan. x: (B, S, Dm)."""
    B, S, Dm = x.shape
    H = cfg.n_heads
    hd = Dm // H

    def body(x, bp):
        hin = L.rms_norm(x, bp["ln1"])
        q = hin @ bp["wq"]
        k = hin @ bp["wk"]
        v = hin @ bp["wv"]
        q = q.reshape(B, S, H, hd)
        k = k.reshape(B, S, H, hd)
        v = v.reshape(B, S, H, hd)
        a = L.gqa_attention(q, k, v, causal=causal)
        x = x + a.reshape(B, S, Dm) @ bp["wo"]
        hin = L.rms_norm(x, bp["ln2"])
        x = x + jax.nn.gelu(hin @ bp["w_in"]) @ bp["w_out"]
        return x, None

    if cfg.unroll_blocks:
        for i in range(cfg.n_blocks):
            bp = jax.tree.map(lambda t: t[i], blocks)
            x, _ = body(x, bp)
    else:
        x, _ = jax.lax.scan(body, x, blocks)
    return x


def _fm_terms(params, ids, cfg):
    """Shared FM machinery. ids: (B, F) -> (linear+fm logit, field embs)."""
    F = cfg.n_sparse
    fidx = jnp.arange(F)[None, :]
    emb = params["tables"][fidx, ids]             # (B, F, D)
    lin = params["linear"][fidx, ids]             # (B, F)
    s = jnp.sum(emb, axis=1)                      # sum-square trick, O(F*D)
    fm = 0.5 * jnp.sum(s * s - jnp.sum(emb * emb, axis=1), axis=-1)
    logit = params["bias"] + jnp.sum(lin, axis=1) + fm
    return logit.astype(jnp.float32), emb


# ---------------------------------------------------------------- scoring --
def forward(params: dict, batch: dict, cfg: RecsysConfig) -> jnp.ndarray:
    """Returns per-example logits.

    deepfm/fm: batch {"sparse_ids": (B, F)}; bst: {"hist": (B, S),
    "target": (B,)}; bert4rec: {"seq": (B, S)} -> (B, S, n_items) logits.
    """
    dt = cfg.param_dtype
    if cfg.kind == "fm":
        logit, _ = _fm_terms(params, batch["sparse_ids"], cfg)
        return logit
    if cfg.kind == "deepfm":
        logit, emb = _fm_terms(params, batch["sparse_ids"], cfg)
        B = emb.shape[0]
        deep = _mlp_head(params["mlp"], emb.reshape(B, -1), dt)[:, 0]
        return logit + deep.astype(jnp.float32)
    if cfg.kind == "bst":
        hist, target = batch["hist"], batch["target"]       # (B,S), (B,)
        B, S = hist.shape
        seq = jnp.concatenate([hist, target[:, None]], axis=1)
        x = params["item_emb"][seq] + params["pos_emb"][None]
        x = _encoder(params["blocks"], x.astype(dt), cfg, causal=False)
        out = _mlp_head(params["mlp"], x.reshape(B, -1), dt)[:, 0]
        return out.astype(jnp.float32)
    if cfg.kind == "bert4rec":
        seq = batch["seq"]                                   # (B, S)
        x = params["item_emb"][seq] + params["pos_emb"][None]
        x = _encoder(params["blocks"], x.astype(dt), cfg, causal=False)
        x = L.rms_norm(x, params["ln_f"])
        logits = x @ params["item_emb"].T.astype(x.dtype)    # tied softmax
        return logits.astype(jnp.float32)
    raise ValueError(cfg.kind)


def loss_fn(params: dict, batch: dict, cfg: RecsysConfig) -> Tuple:
    if cfg.kind == "bert4rec" and cfg.masked_positions > 0:
        return _bert4rec_masked_loss(params, batch, cfg)
    out = forward(params, batch, cfg)
    if cfg.kind == "bert4rec":
        labels = batch["labels"]                             # (B, S), -1 ignore
        mask = labels >= 0
        logp = jax.nn.log_softmax(out, axis=-1)
        nll = -jnp.take_along_axis(
            logp, jnp.maximum(labels, 0)[..., None], axis=-1)[..., 0]
        loss = jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1)
    else:
        y = batch["label"].astype(jnp.float32)
        loss = jnp.mean(
            jnp.maximum(out, 0) - out * y + jnp.log1p(jnp.exp(-jnp.abs(out))))
    return loss, {"loss": loss}


def _bert4rec_masked_loss(params: dict, batch: dict, cfg: RecsysConfig
                          ) -> Tuple:
    """Masked-LM loss evaluated ONLY at masked positions (hillclimb D).

    Baseline materializes (B, S, V) logits — 65536*200*1e6 fp32 is the
    782 GiB/device temp observed in the dry-run. Only ~15% of positions
    carry labels; gathering the <=P labelled encodings per row BEFORE the
    tied-softmax matmul shrinks every logits buffer by S/P. Loss is
    identical whenever a row has <= P masked positions (choose P above the
    masking budget: 0.15*200=30 -> P=40); rows beyond the cap drop excess
    positions (standard fixed-budget masking).
    """
    seq, labels = batch["seq"], batch["labels"]              # (B, S)
    B, S = seq.shape
    P_ = min(cfg.masked_positions, S)
    x = params["item_emb"][seq] + params["pos_emb"][None]
    x = _encoder(params["blocks"], x.astype(cfg.param_dtype), cfg,
                 causal=False)
    x = L.rms_norm(x, params["ln_f"])                        # (B, S, Dm)
    # top-P positions by mask flag (stable w.r.t. position order)
    is_m = (labels >= 0).astype(jnp.int32)
    _, pos = jax.lax.top_k(is_m * (S - jnp.arange(S)) , P_)  # masked first
    xg = jnp.take_along_axis(x, pos[..., None], axis=1)      # (B, P, Dm)
    lg = jnp.take_along_axis(labels, pos, axis=1)            # (B, P)
    logits = (xg @ params["item_emb"].T.astype(xg.dtype)).astype(jnp.float32)
    mask = lg >= 0
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(
        logp, jnp.maximum(lg, 0)[..., None], axis=-1)[..., 0]
    loss = jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1)
    return loss, {"loss": loss}


def serve_step(params: dict, batch: dict, cfg: RecsysConfig) -> jnp.ndarray:
    """Online/bulk scoring: one logit per example.

    fm/deepfm/bst: forward() already is pairwise scoring. bert4rec: scoring
    a (user-sequence, candidate) pair = dot of the last-position encoding
    with the candidate's item embedding (standard eval protocol; computing
    the full (B, S, V) softmax for serving would be nonsense at V=10^6).
    batch for bert4rec: {"seq": (B, S), "cand": (B,)}.
    """
    if cfg.kind != "bert4rec":
        return forward(params, batch, cfg)
    u = query_vector(params, batch, cfg)                     # (B, Dm)
    c = params["item_emb"][batch["cand"]].astype(jnp.float32)
    return jnp.sum(u * c, axis=-1)


# --------------------------------------------------------------- retrieval -
def query_vector(params: dict, batch: dict, cfg: RecsysConfig) -> jnp.ndarray:
    """User/query embedding for retrieval (the ANN query).

    fm/deepfm: sum of user-field embedding vectors — FM's score of item i
    against user fields is <v_i, sum_f v_f> + lin_i, so retrieval reduces
    exactly to inner-product search (Rendle's trick).
    bst/bert4rec: sequence-encoder output at the last position (SASRec-style
    next-item retrieval).
    """
    dt = cfg.param_dtype
    if cfg.kind in ("fm", "deepfm"):
        _, emb = _fm_terms(params, batch["sparse_ids"], cfg)
        return jnp.sum(emb, axis=1).astype(jnp.float32)      # (B, D)
    if cfg.kind == "bst":
        hist = batch["hist"]
        x = params["item_emb"][hist] + params["pos_emb"][None, :hist.shape[1]]
        x = _encoder(params["blocks"], x.astype(dt), cfg, causal=False)
        return x[:, -1].astype(jnp.float32)
    if cfg.kind == "bert4rec":
        seq = batch["seq"]
        x = params["item_emb"][seq] + params["pos_emb"][None]
        x = _encoder(params["blocks"], x.astype(dt), cfg, causal=False)
        x = L.rms_norm(x, params["ln_f"])
        return x[:, -1].astype(jnp.float32)
    raise ValueError(cfg.kind)


def candidate_table(params: dict, cfg: RecsysConfig) -> jnp.ndarray:
    """The corpus being searched in retrieval_cand."""
    if cfg.kind in ("fm", "deepfm"):
        # item corpus = embeddings of field 0 (the "item id" field)
        return params["tables"][0].astype(jnp.float32)       # (V, D)
    return params["item_emb"].astype(jnp.float32)            # (n_items, Dm)


def serve_retrieval(params: dict, batch: dict, cfg: RecsysConfig, k: int = 100,
                    use_kernel: bool = False, shard_topk: int = 0,
                    shard_axis: str = "model"
                    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Exact retrieval: 1-to-B batched inner product over all candidates
    (the paper's H1 workload at B = n_candidates) + top-k. The sub-linear
    alternative builds a KBest index over candidate_table() — see
    examples/retrieval_recsys.py.

    shard_topk > 0 (hillclimb C): the candidate table is row-sharded over
    `shard_axis`; the naive path makes XLA all-gather the FULL (B, V) score
    row to run the global top-k. Instead reshape scores into (B, S, V/S)
    pinned so chunk s lives on shard s, take a LOCAL top-k per shard (the
    exact pattern of core.sharded's sharded search merge), and only the
    (B, S*k) candidates cross the interconnect — V/(S*k) ~ 600x less.
    """
    q = query_vector(params, batch, cfg)                     # (B, D)
    cands = candidate_table(params, cfg)                     # (V, D)
    if use_kernel:
        from repro.kernels import ops as kops
        d = kops.batch_dist(q, cands, metric="ip")
    else:
        d = -(q @ cands.T)
    if shard_topk > 1:
        from jax.sharding import PartitionSpec as P
        B, V = d.shape
        S = shard_topk
        ds_ = d.reshape(B, S, V // S)
        ds_ = jax.lax.with_sharding_constraint(ds_, P(None, shard_axis, None))
        neg_l, ids_l = jax.lax.top_k(-ds_, k)                # local top-k
        base = (jnp.arange(S, dtype=jnp.int32) * (V // S))[None, :, None]
        ids_l = ids_l + base
        neg_l = jax.lax.with_sharding_constraint(
            neg_l, P(None, shard_axis, None))
        neg, pos = jax.lax.top_k(neg_l.reshape(B, S * k), k)  # global merge
        ids = jnp.take_along_axis(ids_l.reshape(B, S * k), pos, axis=1)
        return -neg, ids
    neg, ids = jax.lax.top_k(-d, k)
    return -neg, ids


def serve_retrieval_shardmap(params: dict, batch: dict, cfg: RecsysConfig,
                             mesh, k: int = 100, axis: str = "model"
                             ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Explicit-collective retrieval (hillclimb C, the paper's distributed
    search merge): scores and top-k are computed PER candidate shard under
    shard_map, so only (n_shards, B, k) candidate tuples ever cross the
    interconnect — the GSPMD/Shardy auto-partitioner was observed to
    all-gather the full (B, V) score row instead (V/(n*k) ~ 600x more).
    Identical results to serve_retrieval (exact search)."""
    from jax.sharding import PartitionSpec as P

    q = query_vector(params, batch, cfg)                     # (B, D) repl.
    cands = candidate_table(params, cfg)                     # (V, D) sharded

    def local(q_l, c_l):
        d = -(q_l @ c_l.T)                                   # (B, V/n)
        neg, ids = jax.lax.top_k(-d, k)                      # local top-k
        off = jax.lax.axis_index(axis) * c_l.shape[0]
        ids = ids + off
        all_neg = jax.lax.all_gather(neg, axis)              # (n, B, k)
        all_ids = jax.lax.all_gather(ids, axis)
        n = all_neg.shape[0]
        B = q_l.shape[0]
        flat_neg = all_neg.transpose(1, 0, 2).reshape(B, n * k)
        flat_ids = all_ids.transpose(1, 0, 2).reshape(B, n * k)
        mneg, pos = jax.lax.top_k(flat_neg, k)
        return -mneg, jnp.take_along_axis(flat_ids, pos, axis=1)

    fn = jax.shard_map(local, mesh=mesh,
                       in_specs=(P(), P(axis, None)),
                       out_specs=(P(), P()), check_vma=False)
    return fn(q, cands)
