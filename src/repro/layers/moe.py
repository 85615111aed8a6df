"""Mixture-of-Experts FFN with sort-based dispatch (DESIGN.md §7).

Dense one-hot dispatch einsums (GShard-style) cost O(T·E·C) extra work —
untenable at E=384 (kimi-k2). The sort-based path is O(T·k log(T·k)) for
the permutation plus the unavoidable O(T·k·d·f) expert math:

  router top-k -> flatten (T*k) assignments -> stable-sort by expert ->
  per-expert positions via exclusive-scan of counts -> capacity-drop ->
  scatter token ids into an (E, C) slot buffer -> gather tokens (E, C, d)
  -> batched expert GEMMs -> weighted scatter-add back to (T, d).

Expert parallelism: the (E, ...) leading axis of both the slot buffer and
the expert weights is what the sharding rules map to the mesh's EP axis;
GSPMD then materializes the dispatch/return all-to-alls at the boundary.

Capacity C = ceil(T*k/E * capacity_factor); overflow tokens are dropped
(standard). Aux load-balancing loss (Switch-style) is returned for training.
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.layers.common import act_fn


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    d_ff_expert: int
    n_shared_experts: int = 0
    capacity_factor: float = 1.25
    gated: bool = True           # SwiGLU experts
    act: str = "silu"
    router_aux_weight: float = 0.01
    # Explicit EP/TP layout constraints (hillclimb A, EXPERIMENTS.md §Perf).
    # The expensive mistakes GSPMD makes without them, observed in the
    # kimi-k2 dry-run HLO:
    #   * the (E*C, d) dispatch buffer (top_k x more rows than tokens!) is
    #     all-gathered to full d for the column-parallel w_in GEMM
    #     (17.5 GiB/layer) — gathering the (T, d) token buffer BEFORE
    #     dispatch duplication is top_k x cheaper;
    #   * the row-parallel w_out partial sums are all-reduced on the
    #     (E, C, d) buffer (18.8 GiB/layer) — reduce-scattering to d-shards
    #     and combining back to tokens in shards defers the all-gather to
    #     the (T, d) residual (0.9 GiB).
    # Empty strings = unconstrained (CPU tests / single-device meshes).
    ep_axis: str = ""            # mesh axis experts are sharded over
    tp_axis: str = ""            # mesh axis expert d_ff is sharded over
    token_axes: tuple = ()       # mesh axes the flat token dim is sharded on
    # explicit-collective dispatch (moe_ffn_shardmap): every collective is
    # hand-placed (all_to_all over EP, psums over TP, final all-gather) —
    # the auto-partitioned path's backward-transpose collectives are
    # unreachable via primal constraints (EXPERIMENTS.md §Perf A3).
    use_shardmap: bool = False
    ep_size: int = 0             # static mesh-axis sizes (shard_map needs
    tp_size: int = 0             # them at trace time)


def init_moe(key, d_model: int, cfg: MoEConfig, dtype=jnp.float32) -> dict:
    ks = jax.random.split(key, 5)
    E, f = cfg.n_experts, cfg.d_ff_expert
    s_in = 1.0 / (d_model ** 0.5)
    s_out = 1.0 / (f ** 0.5)
    p = {
        "router": (jax.random.normal(ks[0], (d_model, E)) * s_in).astype(jnp.float32),
        "w_in": (jax.random.normal(ks[1], (E, d_model, f)) * s_in).astype(dtype),
        "w_out": (jax.random.normal(ks[2], (E, f, d_model)) * s_out).astype(dtype),
    }
    if cfg.gated:
        p["w_gate"] = (jax.random.normal(ks[3], (E, d_model, f)) * s_in).astype(dtype)
    if cfg.n_shared_experts:
        fs = f * cfg.n_shared_experts
        p["shared_w_in"] = (jax.random.normal(ks[4], (d_model, fs)) * s_in).astype(dtype)
        p["shared_w_gate"] = (jax.random.normal(ks[0], (d_model, fs)) * s_in).astype(dtype)
        p["shared_w_out"] = (jax.random.normal(ks[1], (fs, d_model)) * s_out).astype(dtype)
    return p


def moe_ffn(params: dict, x: jnp.ndarray, cfg: MoEConfig
            ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: (T, d) flattened tokens -> (out (T, d), aux_loss scalar)."""
    from jax.sharding import PartitionSpec as P

    def cs(t, spec):
        return jax.lax.with_sharding_constraint(t, spec) if cfg.ep_axis else t

    tok = tuple(cfg.token_axes) or None
    # keep d SHARDED over TP through the whole dispatch: the top_k-duplicated
    # buffers then move d/tp-sized slices (the 16x token replication across
    # the TP axis was the dominant collective volume in the baseline HLO)
    x = cs(x, P(tok, cfg.tp_axis or None))
    T, d = x.shape
    E, K = cfg.n_experts, cfg.top_k
    C = max(1, int(T * K / E * cfg.capacity_factor))
    act = act_fn(cfg.act)

    logits = x.astype(jnp.float32) @ params["router"]            # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    w, eidx = jax.lax.top_k(probs, K)                            # (T, K)
    w = w / jnp.maximum(jnp.sum(w, axis=-1, keepdims=True), 1e-9)

    # ---- aux load-balance loss (Switch eq. 4) ----
    frac_tokens = jnp.mean(
        jax.nn.one_hot(eidx[:, 0], E, dtype=jnp.float32), axis=0)
    frac_probs = jnp.mean(probs, axis=0)
    aux = cfg.router_aux_weight * E * jnp.sum(frac_tokens * frac_probs)

    # ---- sort-based dispatch ----
    flat_e = eidx.reshape(-1)                                    # (T*K,)
    flat_t = jnp.repeat(jnp.arange(T, dtype=jnp.int32), K)
    flat_w = w.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    se, st, sw = flat_e[order], flat_t[order], flat_w[order]

    counts = jax.ops.segment_sum(jnp.ones_like(se, jnp.int32), se,
                                 num_segments=E)                 # (E,)
    starts = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                              jnp.cumsum(counts)[:-1]])
    pos_in_e = jnp.arange(T * K, dtype=jnp.int32) - starts[se]
    keep = pos_in_e < C
    slot = jnp.where(keep, se * C + pos_in_e, E * C)             # drop -> sentinel

    buf_tok = jnp.full((E * C + 1,), -1, jnp.int32).at[slot].set(
        jnp.where(keep, st, -1))[:E * C]
    tok_valid = buf_tok >= 0
    # NB: the zero literal must carry x.dtype — a bare 0.0 weak-f32 would
    # promote the whole expert pipeline (and its gradients) to f32: 2x the
    # MXU time and 2x the all-reduce bytes (found via the dry-run HLO).
    xe = jnp.where(tok_valid[:, None], x[jnp.maximum(buf_tok, 0)],
                   jnp.zeros((), x.dtype))
    xe = xe.reshape(E, C, d)

    tp = cfg.tp_axis or None
    # pin the dispatch buffer to EP x TP: E on the EP axis, d on the TP
    # axis. d-sharding makes the w_in GEMM a 2-D contraction whose psum is
    # the SMALL (E, C, f/tp) partial, and makes the backward dxe a
    # reduce-scatter instead of an (E, C, d) f32 all-reduce.
    xe = cs(xe, P(cfg.ep_axis or None, None, tp))

    # ---- batched expert GEMMs ----
    # preferred_element_type pins the DOT OUTPUT dtype: GSPMD places the
    # cross-shard partial-sum all-reduce between the dot and any convert,
    # so an f32-preferring dot puts f32 on the wire — observed to double
    # every MoE collective. In-tile MXU accumulation stays f32 regardless.
    pet = dict(preferred_element_type=x.dtype)
    h = jnp.einsum("ecd,edf->ecf", xe, params["w_in"], **pet)
    if cfg.gated:
        g = jnp.einsum("ecd,edf->ecf", xe, params["w_gate"], **pet)
        h = act(g) * h
    else:
        h = act(h)
    h = cs(h, P(cfg.ep_axis or None, None, tp))
    ye = jnp.einsum("ecf,efd->ecd", h, params["w_out"], **pet)   # (E, C, d)
    # reduce-scatter the row-parallel partials: d stays sharded over TP
    ye = cs(ye, P(cfg.ep_axis or None, None, tp))

    # ---- weighted combine back to tokens ----
    ye_flat = ye.reshape(E * C, d)
    contrib = jnp.where(keep[:, None],
                        ye_flat[jnp.minimum(slot, E * C - 1)]
                        * sw[:, None].astype(ye_flat.dtype),
                        jnp.zeros((), ye_flat.dtype))
    out = jnp.zeros((T, d), ye_flat.dtype).at[st].add(contrib)
    # combine happened in d-shards; the residual add all-gathers (T, d) —
    # top_k x less wire than gathering the capacity buffer
    out = cs(out, P(tok, tp))

    # ---- shared experts (DeepSeek/Kimi style, always-on) ----
    if "shared_w_in" in params:
        hs = x @ params["shared_w_in"]
        gs = x @ params["shared_w_gate"]
        out = out + (act(gs) * hs) @ params["shared_w_out"]

    return out.astype(x.dtype), aux


# ===========================================================================
# Explicit-collective MoE (hillclimb A, landed): shard_map dispatch
# ===========================================================================
def moe_ffn_shardmap(params: dict, x: jnp.ndarray, cfg: MoEConfig
                     ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """moe_ffn with HAND-PLACED collectives under jax.shard_map.

    Layout (mesh axes ep x tp; weights: w_in/w_gate d-sharded over tp,
    w_out f-sharded over tp; experts sharded over ep — see
    sharding/rules.py lm_param_spec(moe_d_sharded=True)):

      per (r, c) device                                  comm (kimi/layer)
      1. route the local (T_l, d) tokens (replicated math)        —
      2. column c dispatches its T_l/Dt token slice into an
         (E, C_l, d) capacity buffer (sort-based, as moe_ffn)     —
      3. all_to_all over ep: (E, C_l, d) -> (E_l, De*C_l, d)   0.6 GiB
      4. h = xr[:, :, d_c] @ w_in_c  -> psum over tp  (x2 gate) 0.3 GiB
      5. ye = h[:, :, f_c] @ w_out_c -> psum over tp            0.6 GiB
      6. all_to_all back over ep                                0.6 GiB
      7. weighted scatter-combine to (T_s, d); all_gather
         the token slices over tp -> (T_l, d)                   0.9 GiB
                                                     total fwd ~3 GiB
    vs ~90 GiB/layer measured on the auto-partitioned baseline. The
    backward transposes each collective mechanically (a2a<->a2a,
    psum<->identity-broadcast, all_gather<->psum_scatter/reduce).

    Capacity is per (expert, column-slice): C_l = ceil(T_s*K/E * factor).
    Results match moe_ffn exactly when no tokens are dropped (tests).
    """
    from jax.sharding import PartitionSpec as P

    ep, tp = cfg.ep_axis, cfg.tp_axis
    De, Dt = cfg.ep_size, cfg.tp_size
    assert De > 0 and Dt > 0, "set MoEConfig.ep_size/tp_size for shardmap"
    E, K = cfg.n_experts, cfg.top_k
    E_l = E // De
    act = act_fn(cfg.act)
    tok = tuple(cfg.token_axes) or (ep,)

    def block(x_l, router, w_in, w_gate, w_out):
        # x_l (T_l, d) full-d; w_in/w_gate (E_l, d_l, f); w_out (E_l, f_l, d)
        T_l, d = x_l.shape
        T_s = T_l // Dt
        C_l = max(1, int(T_s * K / E * cfg.capacity_factor))
        c = jax.lax.axis_index(tp)
        d_l = w_in.shape[1]
        f = w_in.shape[2]
        f_l = w_out.shape[1]

        # ---- 1. routing (local, exact — router replicated) --------------
        logits = x_l.astype(jnp.float32) @ router            # (T_l, E)
        probs = jax.nn.softmax(logits, axis=-1)
        wts, eidx = jax.lax.top_k(probs, K)
        wts = wts / jnp.maximum(jnp.sum(wts, -1, keepdims=True), 1e-9)
        frac_tokens = jnp.mean(
            jax.nn.one_hot(eidx[:, 0], E, dtype=jnp.float32), axis=0)
        aux = cfg.router_aux_weight * E * jnp.sum(
            frac_tokens * jnp.mean(probs, axis=0))
        aux = jax.lax.pmean(jax.lax.pmean(aux, ep), tp)

        # ---- 2. column c dispatches its token slice ----------------------
        x_s = jax.lax.dynamic_slice(x_l, (c * T_s, 0), (T_s, d))
        e_s = jax.lax.dynamic_slice(eidx, (c * T_s, 0), (T_s, K))
        w_s = jax.lax.dynamic_slice(wts, (c * T_s, 0), (T_s, K))
        flat_e = e_s.reshape(-1)
        flat_t = jnp.repeat(jnp.arange(T_s, dtype=jnp.int32), K)
        flat_w = w_s.reshape(-1)
        order = jnp.argsort(flat_e, stable=True)
        se, st, sw = flat_e[order], flat_t[order], flat_w[order]
        counts = jax.ops.segment_sum(jnp.ones_like(se, jnp.int32), se,
                                     num_segments=E)
        starts = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                                  jnp.cumsum(counts)[:-1]])
        pos = jnp.arange(T_s * K, dtype=jnp.int32) - starts[se]
        keep = pos < C_l
        slot = jnp.where(keep, se * C_l + pos, E * C_l)
        buf_tok = jnp.full((E * C_l + 1,), -1, jnp.int32).at[slot].set(
            jnp.where(keep, st, -1))[:E * C_l]
        valid = buf_tok >= 0
        xe = jnp.where(valid[:, None], x_s[jnp.maximum(buf_tok, 0)],
                       jnp.zeros((), x_s.dtype)).reshape(E, C_l, d)

        # ---- 3. dispatch all_to_all over EP ------------------------------
        xr = jax.lax.all_to_all(xe, ep, split_axis=0, concat_axis=1,
                                tiled=True)                   # (E_l, De*C_l, d)

        # ---- 4. expert GEMMs ---------------------------------------------
        # Columns hold DISJOINT token slices, so a d-contraction psum over
        # TP would mix different tokens' partials. First a2a over TP trades
        # the d axis for the token axis (every column: ALL tokens, its d_l
        # slice — same bytes), contract, then psum_scatter hands each
        # column back exactly its own token block of the full-f result.
        C_row = xr.shape[1]
        xr = jax.lax.all_to_all(xr, tp, split_axis=2, concat_axis=1,
                                tiled=True)                # (E_l, Dt*C_row, d_l)
        pet = dict(preferred_element_type=x_l.dtype)
        h = jax.lax.psum_scatter(
            jnp.einsum("ecd,edf->ecf", xr, w_in, **pet), tp,
            scatter_dimension=1, tiled=True)               # (E_l, C_row, f)
        if cfg.gated:
            g = jax.lax.psum_scatter(
                jnp.einsum("ecd,edf->ecf", xr, w_gate, **pet), tp,
                scatter_dimension=1, tiled=True)
            h = act(g) * h
        else:
            h = act(h)

        # ---- 5. down-projection: same trade (f <-> tokens) as step 4 -----
        hh = jax.lax.all_to_all(h, tp, split_axis=2, concat_axis=1,
                                tiled=True)                # (E_l, Dt*C_row, f_l)
        ye = jax.lax.psum_scatter(
            jnp.einsum("ecf,efd->ecd", hh, w_out, **pet), tp,
            scatter_dimension=1, tiled=True)               # (E_l, C_row, d)

        # ---- 6. return all_to_all over EP --------------------------------
        yr = jax.lax.all_to_all(ye, ep, split_axis=1, concat_axis=0,
                                tiled=True)                   # (E, C_l, d)

        # ---- 7. weighted combine + reassemble the token axis over TP -----
        yf = yr.reshape(E * C_l, d)
        contrib = jnp.where(keep[:, None],
                            yf[jnp.minimum(slot, E * C_l - 1)]
                            * sw[:, None].astype(yf.dtype),
                            jnp.zeros((), yf.dtype))
        out_s = jnp.zeros((T_s, d), yf.dtype).at[st].add(contrib)
        out_l = jax.lax.all_gather(out_s, tp, axis=0, tiled=True)  # (T_l, d)
        return out_l, aux

    fn = jax.shard_map(
        block,
        in_specs=(P(tok, None), P(), P(ep, tp, None), P(ep, tp, None),
                  P(ep, tp, None)),
        out_specs=(P(tok, None), P()),
        check_vma=False)
    w_gate = params.get("w_gate", params["w_in"])
    out, aux = fn(x, params["router"], params["w_in"], w_gate,
                  params["w_out"])

    if "shared_w_in" in params:
        hs = x @ params["shared_w_in"]
        gs = x @ params["shared_w_gate"]
        out = out + (act(gs) * hs) @ params["shared_w_out"]
    return out.astype(x.dtype), aux
