"""Mixture-of-Experts FFN with sort-based (megablox-style) routing.

Instead of the classic (tokens × experts × capacity) one-hot dispatch tensor
— infeasible at qwen3's 128 experts — tokens are **sorted by assigned
expert** and gathered into per-expert capacity buckets:

    flatten -> top-k route -> sort by expert -> bucket to (E, C, d)
    -> batched expert matmuls -> scatter-combine with router weights.

The sort is the same contention-free primitive the whole framework is built
on (DESIGN.md §2); under GSPMD the (tokens)[data] → (experts)[model]
re-bucketing lowers to the expected EP all-to-all pair.

Overflowing tokens beyond ``capacity = tokens·k/E · capacity_factor`` are
dropped (their combine weight is zero) — standard capacity-based semantics.
An auxiliary load-balancing loss is returned for training.
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from ..launch.sharding import shard
from .layers import dense_init

Params = Dict


def moe_init(key, d_model: int, d_ff: int, n_experts: int, act: str,
             dtype) -> Params:
    kr, ki, kg, ko = jax.random.split(key, 4)
    scale_in = 1.0 / (d_model ** 0.5)
    scale_out = 1.0 / (d_ff ** 0.5)
    p = {
        "router": dense_init(kr, d_model, n_experts, jnp.float32),
        "wi": (jax.random.normal(ki, (n_experts, d_model, d_ff)) * scale_in
               ).astype(dtype),
        "wo": (jax.random.normal(ko, (n_experts, d_ff, d_model)) * scale_out
               ).astype(dtype),
    }
    if act == "swiglu":
        p["wg"] = (jax.random.normal(kg, (n_experts, d_model, d_ff)) * scale_in
                   ).astype(dtype)
    return p


def moe_apply(p: Params, x: jax.Array, cfg) -> Tuple[jax.Array, jax.Array]:
    """Dispatch on cfg.moe_impl (see module docstring / §Perf)."""
    if getattr(cfg, "moe_impl", "sorted") == "expert_tp":
        out = moe_apply_expert_tp(p, x, cfg)
        if out is not None:
            return out
    return moe_apply_sorted(p, x, cfg)


def moe_apply_sorted(p: Params, x: jax.Array, cfg) -> Tuple[jax.Array, jax.Array]:
    """x: (B, S, d) -> (out, aux_loss)."""
    compute = x.dtype
    b, s, d = x.shape
    e = cfg.n_experts
    k = cfg.experts_per_token
    t = b * s
    capacity = max(int(t * k / e * cfg.capacity_factor), 1)
    # round capacity to an MXU-friendly multiple
    capacity = -(-capacity // 128) * 128 if capacity >= 128 else capacity

    xf = x.reshape(t, d)
    logits = jnp.dot(xf.astype(jnp.float32), p["router"]["w"])     # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, k)                  # (T, k)
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)

    # -- load balance aux (Switch-style)
    me = jnp.mean(probs, axis=0)                                   # (E,)
    ce = jnp.mean(jax.nn.one_hot(gate_idx[:, 0], e), axis=0)
    aux = jnp.sum(me * ce) * e

    # -- sort token-expert assignments by expert (the sort-first trick)
    flat_expert = gate_idx.reshape(-1)                             # (T*k,)
    flat_token = jnp.repeat(jnp.arange(t), k)
    flat_gate = gate_vals.reshape(-1)
    order = jnp.argsort(flat_expert, stable=True)
    se_, st_, sg_ = flat_expert[order], flat_token[order], flat_gate[order]

    # rank of each assignment within its expert group
    seg_start = jnp.searchsorted(se_, jnp.arange(e))               # (E,)
    rank = jnp.arange(t * k) - seg_start[se_]
    keep = rank < capacity                                          # drop overflow

    # bucket index (E, C) -> position in sorted stream
    bucket_pos = seg_start[:, None] + jnp.arange(capacity)[None, :]
    bucket_valid = bucket_pos < jnp.searchsorted(se_, jnp.arange(e),
                                                 side="right")[:, None]
    bucket_pos = jnp.minimum(bucket_pos, t * k - 1)
    bucket_tok = jnp.where(bucket_valid, st_[bucket_pos], 0)        # (E, C)

    xe = xf[bucket_tok] * bucket_valid[..., None].astype(compute)   # (E, C, d)
    # capacity dim shards over data (tokens), expert dim over model (EP):
    # compute is 1/(data·model) per device; the (tokens)[data] ->
    # (experts)[model] re-bucketing is the EP all-to-all.
    xe = shard(xe, ("experts", "batch", "embed"))

    # -- expert FFN (batched over experts; shards over the expert axis)
    wi = p["wi"].astype(compute)
    wo = p["wo"].astype(compute)
    h = jnp.einsum("ecd,edf->ecf", xe, wi, preferred_element_type=compute)
    if "wg" in p:
        g = jnp.einsum("ecd,edf->ecf", xe, p["wg"].astype(compute),
                       preferred_element_type=compute)
        h = jax.nn.silu(g) * h
    else:
        h = jax.nn.gelu(h)
    h = shard(h, ("experts", "batch", "expert_ff"))
    ye = jnp.einsum("ecf,efd->ecd", h, wo, preferred_element_type=compute)
    ye = shard(ye, ("experts", "batch", "embed"))

    # -- combine back: scatter expert outputs to (sorted) assignments
    flat_out = ye.reshape(e * capacity, d)
    assign_bucket = jnp.where(keep, se_ * capacity + jnp.minimum(rank, capacity - 1),
                              0)
    contrib = flat_out[assign_bucket] * (sg_ * keep)[:, None].astype(compute)
    out = jax.ops.segment_sum(contrib, st_, num_segments=t)         # (T, d)
    return out.reshape(b, s, d).astype(compute), aux.astype(jnp.float32)


# ---------------------------------------------------------------------------
# expert-TP implementation (§Perf optimization)
# ---------------------------------------------------------------------------
#
# The sorted/GSPMD path above routes through a *global* argsort over T·k
# sharded assignments and a scatter-add combine; XLA lowers both to repeated
# (T, d)-sized all-reduces — ~850 s of collective time per step at qwen3
# scale (measured, EXPERIMENTS.md §Perf).  This path instead treats the
# expert axis as tensor parallelism:
#
#   * activations are replicated across the model axis anyway (standard TP),
#     so every model shard can bucket ITS experts' tokens locally — no
#     communication to dispatch;
#   * each shard runs its E/m experts over its local data-shard tokens;
#   * one psum over the model axis combines expert outputs — exactly the
#     collective a dense TP FFN already pays.
#
# Capacity semantics become per-(data-shard, expert) — the standard
# practical relaxation.


def moe_apply_expert_tp(p: Params, x: jax.Array, cfg):
    """shard_map MoE: local bucketing, expert-sharded FFN, psum combine.

    Returns None if no mesh/rules are installed (caller falls back)."""
    import functools

    from jax.sharding import PartitionSpec as P

    from ..launch.sharding import current_rules

    rules = current_rules()
    if rules is None or rules.mesh is None:
        return None
    mesh = rules.mesh
    model_axis = rules.mapping.get("experts")
    if model_axis is None:   # experts not sharded: sorted path handles it
        return None
    dp = rules.mapping.get("batch")
    m_size = mesh.shape[model_axis]
    e = cfg.n_experts
    if e % m_size:
        return None
    e_local = e // m_size

    b, s, d = x.shape
    dp_axes = tuple(a for a in ((dp,) if isinstance(dp, str) else (dp or ()))
                    )
    dp_total = 1
    for a in dp_axes:
        dp_total *= mesh.shape[a]
    t_local = (b // dp_total) * s
    k = cfg.experts_per_token
    cap = max(int(t_local * k / e * cfg.capacity_factor), 8)

    x_spec = P(dp, None, None)
    w_spec_in = P(model_axis, rules.mapping.get("w_embed"), None)
    w_spec_out = P(model_axis, None, rules.mapping.get("w_embed"))
    r_spec = P(rules.mapping.get("w_embed"), None)

    has_gate = "wg" in p
    in_specs = [x_spec, r_spec, w_spec_in, w_spec_out]
    args = [x, p["router"]["w"], p["wi"], p["wo"]]
    if has_gate:
        in_specs.append(w_spec_in)
        args.append(p["wg"])

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=tuple(in_specs),
        out_specs=(P(dp, None, None), P()),
        check_vma=False)
    def run(x_l, router_w, wi, wo, *rest):
        wg = rest[0] if rest else None
        compute = x_l.dtype
        bl = x_l.shape[0]
        xf = x_l.reshape(bl * s, d)                       # local tokens
        tl = xf.shape[0]
        # router weights may be d-sharded (2D weights): gather them
        if w_spec_in[1] is not None:
            router_w = jax.lax.all_gather(
                router_w, w_spec_in[1], axis=0, tiled=True)
            wi = jax.lax.all_gather(wi, w_spec_in[1], axis=1, tiled=True)
            wo = jax.lax.all_gather(wo, w_spec_out[2], axis=2, tiled=True)
            if wg is not None:
                wg = jax.lax.all_gather(wg, w_spec_in[1], axis=1, tiled=True)
        logits = jnp.dot(xf.astype(jnp.float32), router_w)       # (tl, E)
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, gate_idx = jax.lax.top_k(probs, k)
        gate_vals = gate_vals / jnp.maximum(
            jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)

        me_ = jnp.mean(probs, axis=0)
        ce_ = jnp.mean(jax.nn.one_hot(gate_idx[:, 0], e), axis=0)
        aux = jnp.sum(me_ * ce_) * e

        # my experts: [e0, e0 + e_local)
        mi = jax.lax.axis_index(model_axis)
        e0 = mi * e_local
        # rank of each (token, slot) within its expert via sorted positions
        flat_e = gate_idx.reshape(-1)                             # (tl·k,)
        order = jnp.argsort(flat_e, stable=True)                  # local sort
        se_ = flat_e[order]
        st_ = (jnp.repeat(jnp.arange(tl), k))[order]
        sg_ = gate_vals.reshape(-1)[order]
        seg_start = jnp.searchsorted(se_, jnp.arange(e))
        # bucket my experts' assignments into (e_local, cap)
        bucket_pos = seg_start[e0 + jnp.arange(e_local)][:, None] \
            + jnp.arange(cap)[None, :]
        seg_end = jnp.searchsorted(se_, jnp.arange(e), side="right")
        bucket_valid = bucket_pos < seg_end[e0 + jnp.arange(e_local)][:, None]
        bucket_pos = jnp.minimum(bucket_pos, tl * k - 1)
        bucket_tok = jnp.where(bucket_valid, st_[bucket_pos], 0)
        bucket_gate = jnp.where(bucket_valid, sg_[bucket_pos], 0.0)

        xe = xf[bucket_tok] * bucket_valid[..., None].astype(compute)
        wi_l = wi.astype(compute)
        h = jnp.einsum("ecd,edf->ecf", xe, wi_l,
                       preferred_element_type=compute)
        if wg is not None:
            g = jnp.einsum("ecd,edf->ecf", xe, wg.astype(compute),
                           preferred_element_type=compute)
            h = jax.nn.silu(g) * h
        else:
            h = jax.nn.gelu(h)
        ye = jnp.einsum("ecf,efd->ecd", h, wo.astype(compute),
                        preferred_element_type=compute)
        # weighted scatter back to local tokens (local segment_sum)
        contrib = (ye * bucket_gate[..., None].astype(compute)
                   ).reshape(e_local * cap, d)
        out = jax.ops.segment_sum(contrib, bucket_tok.reshape(-1),
                                  num_segments=tl)
        # combine across expert shards — the TP-FFN psum
        out = jax.lax.psum(out.astype(compute), model_axis)
        aux = jax.lax.pmean(aux, model_axis)
        if dp_axes:
            aux = jax.lax.pmean(aux, dp_axes)
        return out.reshape(bl, s, d), aux.astype(jnp.float32)

    out, aux = run(*args)
    return out, aux
