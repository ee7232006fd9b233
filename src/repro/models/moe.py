"""Mixture-of-Experts block with capacity-based sort dispatch.

BitGNN tie-in (DESIGN.md §4): the token->(expert,slot) assignment built here
IS a binary sparse matrix D in {0,1}^(tokens x E*C); dispatch is D^T @ X and
combine is (D * gates) @ Y — the paper's BSpMM.FBF with "unweighted
adjacency" semantics. On TPU we realize D^T@X as gather/scatter (XLA lowers
to all-to-all under expert sharding), which is the dense-index equivalent of
the FRDC kernel's neighbor gather; the GNN stack exercises the actual packed
BSpMM kernel.

Experts are sharded over the ``model`` axis (EP); counts are padded to a
multiple of TP by ``resolve_for_mesh`` and padded experts are masked out of
routing (their FLOPs show up in the roofline useful-ratio, not in quality).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from .layers import _init, linear


def _maybe_constrain(x, *spec):
    """Apply a PartitionSpec constraint iff a mesh context is active (the
    dry-run / pjit path); no-op for single-device tests."""
    try:
        mesh = jax.sharding.get_abstract_mesh()
        if mesh is not None and "model" in (mesh.axis_names or ()):
            return jax.lax.with_sharding_constraint(
                x, jax.sharding.PartitionSpec(*spec))
    except Exception:
        pass
    return x


def init_moe(key, cfg: ModelConfig, dtype):
    d, ff = cfg.d_model, cfg.d_ff
    e = cfg.moe_experts_padded or cfg.moe_experts
    ks = jax.random.split(key, 6)
    ff_in = 2 * ff if cfg.act == "swiglu" else ff
    p = {
        "router": _init(ks[0], (d, e), d, jnp.float32),
        "wi": _init(ks[1], (e, d, ff_in), d, dtype),
        "wo": _init(ks[2], (e, ff, d), ff, dtype),
    }
    if cfg.moe_shared_ff:
        sf = cfg.moe_shared_ff
        p["shared_wi"] = _init(ks[3], (d, 2 * sf if cfg.act == "swiglu" else sf),
                               d, dtype)
        p["shared_wo"] = _init(ks[4], (sf, d), sf, dtype)
        p["shared_gate"] = _init(ks[5], (d, 1), d, dtype)
    return p


def _capacity(n_tokens: int, n_experts: int, top_k: int, factor: float) -> int:
    c = int(n_tokens * top_k * factor / n_experts)
    return max(-(-c // 8) * 8, 8)


def moe_block(params, x: jax.Array, cfg: ModelConfig):
    """x: (B, T, d) -> (B, T, d). Dispatch is GLOBAL by default;
    ``cfg.moe_groups > 1`` switches to per-data-shard grouped dispatch;
    ``cfg.moe_groups == -1`` uses the shard_map implementation (§Perf A3:
    per-device local routing + expert compute + ONE tensor-parallel psum —
    no global token gathers at all)."""
    if getattr(cfg, "moe_groups", 0) == -1:
        return _moe_shard_map(params, x, cfg)
    if getattr(cfg, "moe_groups", 0) > 1:
        return _moe_grouped(params, x, cfg)
    b, t, d = x.shape
    n = b * t
    e = cfg.moe_experts_padded or cfg.moe_experts
    k = cfg.moe_top_k
    flat = x.reshape(n, d)

    logits = (flat.astype(jnp.float32) @ params["router"])        # (N, E)
    if e > cfg.moe_experts:  # mask padded experts out of routing
        pad = jnp.full((e - cfg.moe_experts,), -1e9, logits.dtype)
        logits = logits + jnp.concatenate(
            [jnp.zeros((cfg.moe_experts,), logits.dtype), pad])[None, :]
    gates_all = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(gates_all, k)           # (N, k)
    gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)

    cap = _capacity(n, e, k, cfg.capacity_factor)

    # ---- build the binary dispatch matrix D (sorted-rank formulation) ----
    fe = expert_idx.reshape(-1)                                    # (N*k,)
    ft = jnp.repeat(jnp.arange(n), k)
    fg = gate_vals.reshape(-1).astype(x.dtype)
    order = jnp.argsort(fe, stable=True)
    se, st, sg = fe[order], ft[order], fg[order]
    starts = jnp.searchsorted(se, jnp.arange(e))
    rank = jnp.arange(n * k) - starts[se]
    keep = rank < cap
    slot = jnp.where(keep, se * cap + rank, e * cap)               # trash slot

    # dispatch: Xe = D^T @ X  (binary-sparse x dense — BSpMM.FBF semantics)
    xe = jnp.zeros((e * cap + 1, d), x.dtype).at[slot].set(flat[st])
    xe = xe[:-1].reshape(e, cap, d)
    # EP x DP: experts over "model", capacity rows over the dp axes — the
    # dispatch scatter becomes the MoE all-to-all.
    xe = _maybe_constrain(xe, "model", "data", None)

    # expert FFNs (EP-sharded einsums)
    h = jnp.einsum("ecd,edf->ecf", xe, params["wi"])
    if cfg.act == "swiglu":
        g, u = jnp.split(h, 2, axis=-1)
        h = jax.nn.silu(g) * u
    else:
        h = jax.nn.gelu(h)
    ye = jnp.einsum("ecf,efd->ecd", h, params["wo"])

    # combine: Y = (D * gates) @ Ye
    y_tok = ye.reshape(e * cap, d)[jnp.minimum(slot, e * cap - 1)]
    y_tok = y_tok * (sg * keep.astype(x.dtype))[:, None]
    out = jnp.zeros((n, d), x.dtype).at[st].add(y_tok)

    if "shared_wi" in params:
        out = out + _shared_expert(params, flat, cfg)
    return out.reshape(b, t, d)


def _shared_expert(params, flat, cfg):
    h = linear(params["shared_wi"], flat)
    if cfg.act == "swiglu":
        g, u = jnp.split(h, 2, axis=-1)
        h = jax.nn.silu(g) * u
    else:
        h = jax.nn.gelu(h)
    shared = linear(params["shared_wo"], h)
    sgate = jax.nn.sigmoid(flat @ params["shared_gate"])
    return shared * sgate


def _moe_grouped(params, x: jax.Array, cfg: ModelConfig):
    """Per-dp-shard dispatch (§Perf): tokens are split into ``moe_groups``
    groups aligned with the dp axis; routing, capacity, sort, gather and
    combine are all group-local, so the only cross-device traffic is the
    expert-parallel exchange of the (G, E, cap_loc, d) dispatch buffer —
    no global token all-gather."""
    b, t, d = x.shape
    n = b * t
    g = cfg.moe_groups
    e = cfg.moe_experts_padded or cfg.moe_experts
    k = cfg.moe_top_k
    nl = n // g
    flat = x.reshape(g, nl, d)
    flat = _maybe_constrain(flat, "data", None, None)

    logits = flat.astype(jnp.float32) @ params["router"]          # (G,NL,E)
    if e > cfg.moe_experts:
        pad = jnp.full((e - cfg.moe_experts,), -1e9, logits.dtype)
        logits = logits + jnp.concatenate(
            [jnp.zeros((cfg.moe_experts,), logits.dtype), pad])[None, None, :]
    gates_all = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(gates_all, k)           # (G,NL,k)
    gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)
    cap = _capacity(nl, e, k, cfg.capacity_factor)

    fe = expert_idx.reshape(g, nl * k)
    ft = jnp.broadcast_to(jnp.repeat(jnp.arange(nl), k)[None], (g, nl * k))
    fg = gate_vals.reshape(g, nl * k).astype(x.dtype)
    order = jnp.argsort(fe, axis=-1, stable=True)
    se = jnp.take_along_axis(fe, order, axis=-1)
    st = jnp.take_along_axis(ft, order, axis=-1)
    sg = jnp.take_along_axis(fg, order, axis=-1)
    starts = jax.vmap(lambda row: jnp.searchsorted(row, jnp.arange(e)))(se)
    rank = jnp.arange(nl * k)[None, :] - jnp.take_along_axis(starts, se, axis=-1)
    keep = rank < cap
    slot = jnp.where(keep, se * cap + rank, e * cap)

    gi = jnp.arange(g)[:, None]
    xe = jnp.zeros((g, e * cap + 1, d), x.dtype)
    xe = xe.at[gi, slot].set(jnp.take_along_axis(
        flat, st[..., None], axis=1))
    xe = xe[:, :-1].reshape(g, e, cap, d)
    xe = _maybe_constrain(xe, "data", "model", None, None)

    h = jnp.einsum("gecd,edf->gecf", xe, params["wi"])
    if cfg.act == "swiglu":
        gg, u = jnp.split(h, 2, axis=-1)
        h = jax.nn.silu(gg) * u
    else:
        h = jax.nn.gelu(h)
    ye = jnp.einsum("gecf,efd->gecd", h, params["wo"])
    ye = _maybe_constrain(ye, "data", "model", None, None)

    y_rows = ye.reshape(g, e * cap, d)[gi, jnp.minimum(slot, e * cap - 1)]
    y_rows = y_rows * (sg * keep.astype(x.dtype))[..., None]
    out = jnp.zeros((g, nl, d), x.dtype).at[gi, st].add(y_rows)
    out = _maybe_constrain(out, "data", None, None)
    out = out.reshape(n, d)

    if "shared_wi" in params:
        out = out + _shared_expert(params, x.reshape(n, d), cfg)
    return out.reshape(b, t, d)


def _moe_shard_map(params, x: jax.Array, cfg: ModelConfig):
    """§Perf A3: explicit-SPMD MoE.

    Every device holds a data-shard of tokens (replicated across the model
    axis) and a model-shard of experts. Each device routes ITS tokens, keeps
    only assignments to ITS experts (local mask + local capacity slots — a
    purely local binary dispatch matrix, the paper's BSpMM operand), runs its
    expert FFNs, combines locally, and a single ``psum`` over the model axis
    adds up per-expert partial outputs. Collectives per layer: ONE (nl, d)
    all-reduce — no token all-gathers.
    """
    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or "model" not in (mesh.axis_names or ()):
        return moe_block(params, x,
                         __import__("dataclasses").replace(cfg, moe_groups=0))
    dp_axes = tuple(a for a in mesh.axis_names if a != "model")
    b, t, d = x.shape
    n = b * t
    e = cfg.moe_experts_padded or cfg.moe_experts
    k = cfg.moe_top_k
    tp = mesh.shape["model"]
    e_loc = e // tp
    P = jax.sharding.PartitionSpec

    def body(flat, router, wi, wo):
        # flat (nl, d) local tokens; wi (e_loc, d, ff_in); wo (e_loc, ff, d)
        nl = flat.shape[0]
        m_idx = jax.lax.axis_index("model")
        e0 = m_idx * e_loc
        logits = flat.astype(jnp.float32) @ router                 # (nl, E)
        if e > cfg.moe_experts:
            pad = jnp.full((e - cfg.moe_experts,), -1e9, logits.dtype)
            logits = logits + jnp.concatenate(
                [jnp.zeros((cfg.moe_experts,), logits.dtype), pad])[None]
        gates_all = jax.nn.softmax(logits, axis=-1)
        gate_vals, expert_idx = jax.lax.top_k(gates_all, k)
        gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)
        cap = _capacity(nl, e, k, cfg.capacity_factor)

        fe = expert_idx.reshape(-1)
        ft = jnp.repeat(jnp.arange(nl), k)
        fg = gate_vals.reshape(-1).astype(flat.dtype)
        order = jnp.argsort(fe, stable=True)
        se, st, sg = fe[order], ft[order], fg[order]
        starts = jnp.searchsorted(se, jnp.arange(e))
        rank = jnp.arange(nl * k) - starts[se]
        local = (se >= e0) & (se < e0 + e_loc) & (rank < cap)
        slot = jnp.where(local, (se - e0) * cap + rank, e_loc * cap)

        xe = jnp.zeros((e_loc * cap + 1, d), flat.dtype).at[slot].set(flat[st])
        xe = xe[:-1].reshape(e_loc, cap, d)
        h = jnp.einsum("ecd,edf->ecf", xe, wi)
        if cfg.act == "swiglu":
            g_, u = jnp.split(h, 2, axis=-1)
            h = jax.nn.silu(g_) * u
        else:
            h = jax.nn.gelu(h)
        ye = jnp.einsum("ecf,efd->ecd", h, wo)
        y_rows = ye.reshape(e_loc * cap, d)[jnp.minimum(slot, e_loc * cap - 1)]
        y_rows = y_rows * (sg * local.astype(flat.dtype))[:, None]
        out = jnp.zeros((nl, d), flat.dtype).at[st].add(y_rows)
        return jax.lax.psum(out, "model")

    flat = x.reshape(n, d)
    out = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(dp_axes, None), P(None, None),
                  P("model", None, None), P("model", None, None)),
        out_specs=P(dp_axes, None), check_vma=False,
    )(flat, params["router"], params["wi"], params["wo"])
    if "shared_wi" in params:
        out = out + _shared_expert(params, flat, cfg)
    return out.reshape(b, t, d)
