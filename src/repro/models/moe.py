"""Mixture-of-Experts with expert parallelism.

Three execution paths, selected per call-site conditions (the dispatch mode
is also a design-point axis for the Generator):

  dense  — every expert on every token, weighted by top-k gates. Exact, no
           mesh needed. Used for smoke tests and as the numerical oracle.
  gather — all_gather the (few) tokens over the expert-sharding axes, each
           device computes its local expert shard for all tokens, then
           psum-combines. No capacity drops; right for decode steps.
  a2a    — production expert parallelism: sequence-split tokens over the
           "model" axis, capacity-bucketed scatter into per-expert slots,
           all_to_all over the expert-sharding axes (one hop per mesh axis:
           "model", then also "data" for 256-way EP à la DeepSeek-V3), local
           expert GEMMs, reverse all_to_all, weighted combine, all_gather
           back to the full sequence.

Expert weights are stacked (E_pad, d, f) with the E axis sharded over
``cfg.moe.ep_axes``; E is padded (config-time) so every mesh divides it.
Capacity-overflow tokens are dropped (switch-transformer semantics) via
scatter ``mode="drop"`` / gather ``mode="fill"``.

An expert share (``MoEConfig.experts_held`` < E): the chip holds experts
[first_expert, first_expert + held) of an expert-parallel deployment. The
router keeps its E outputs and its top-k; the dense path computes only the
held experts' part of the result, for every token it routes to them, and
drops none. What the absent experts would add is left out: there is no
exchange on one chip.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.models.params import ParamDef
from repro.models.quant import qeinsum
from repro.sharding.rules import active_mesh, batch_axes


def _epad(cfg: ArchConfig) -> int:
    m = cfg.moe
    return m.padded_experts or m.num_experts


def moe_defs(cfg: ArchConfig) -> dict:
    m = cfg.moe
    d, f, ep, held = cfg.d_model, m.expert_d_ff, _epad(cfg), m.held
    defs = {
        "router": ParamDef((d, ep), (None, None), dtype=jnp.float32),
        "wg": ParamDef((held, d, f), ("experts", "embed", None)),
        "wu": ParamDef((held, d, f), ("experts", "embed", None)),
        "wd": ParamDef((held, f, d), ("experts", None, "embed")),
    }
    if m.num_shared:
        shared_f = m.shared_d_ff * m.num_shared
        defs["shared"] = {
            "wg": ParamDef((d, shared_f), ("embed", "mlp")),
            "wu": ParamDef((d, shared_f), ("embed", "mlp")),
            "wd": ParamDef((shared_f, d), ("mlp", "embed")),
        }
    return defs


def _router(params, x2d, cfg: ArchConfig):
    """x2d: (T, D) → top-k weights (T,k), ids (T,k), probs (T,E_pad) f32."""
    m = cfg.moe
    logits = x2d.astype(jnp.float32) @ params["router"].astype(jnp.float32)
    ep = logits.shape[-1]
    if ep > m.num_experts:  # mask config-time padding experts
        pad_mask = jnp.arange(ep) < m.num_experts
        logits = jnp.where(pad_mask[None, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    w, ids = jax.lax.top_k(probs, m.top_k)
    w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)  # renormalize top-k
    return w, ids, probs


def _expert_ffn(wg, wu, wd, x, cfg: ArchConfig):
    """Batched expert GEMMs. x: (E_loc, C, D) → (E_loc, C, D)."""
    from repro.models.activations import get_activation

    act = get_activation(cfg.activation, cfg.activation_impl)
    g = qeinsum("ecd,edf->ecf", x, wg)
    u = qeinsum("ecd,edf->ecf", x, wu)
    return qeinsum("ecf,efd->ecd", act(g) * u, wd)


def _shared_ffn(shared, x, cfg: ArchConfig):
    """Shared-expert MLP without sharding constraints (shard_map-safe)."""
    from repro.models.activations import get_activation

    act = get_activation(cfg.activation, cfg.activation_impl)
    g = qeinsum("bsd,df->bsf", x, shared["wg"])
    u = qeinsum("bsd,df->bsf", x, shared["wu"])
    return qeinsum("bsf,fd->bsd", act(g) * u, shared["wd"])


def _aux_loss(probs, ids, cfg: ArchConfig):
    """Switch-style load-balance loss (computed over local tokens)."""
    m = cfg.moe
    e = probs.shape[-1]
    counts = jnp.sum(jax.nn.one_hot(ids, e, dtype=jnp.float32), axis=tuple(range(ids.ndim)))
    frac = counts / jnp.maximum(counts.sum(), 1.0)
    mean_prob = probs.reshape(-1, e).mean(axis=0)
    return m.num_experts * jnp.sum(frac * mean_prob)


# ---------------------------------------------------------------------------
# dense path (oracle / smoke)
# ---------------------------------------------------------------------------
def _moe_dense(params, x, cfg: ArchConfig):
    """Every held expert on every token, weighted by the token's gate for it
    (0 where the token did not choose it): exact, and no token dropped."""
    b, s, d = x.shape
    xf = x.reshape(b * s, d)
    with jax.named_scope("moe"):
        w, ids, probs = _router(params, xf, cfg)
        ep, held, first = _epad(cfg), cfg.moe.held, cfg.moe.first_expert
        h = _expert_ffn(
            params["wg"], params["wu"], params["wd"],
            jnp.broadcast_to(xf[None], (held, b * s, d)), cfg,
        )  # (E_held, T, D)
        gates = jnp.zeros((b * s, ep), x.dtype)
        gates = gates.at[jnp.arange(b * s)[:, None], ids].set(w.astype(x.dtype))
        if held < ep:
            gates = gates[:, first : first + held]
        y = jnp.einsum("te,etd->td", gates, h)
    return y.reshape(b, s, d), _aux_loss(probs, ids, cfg)


# ---------------------------------------------------------------------------
# sharded paths (run per-device inside shard_map)
# ---------------------------------------------------------------------------
def _positions_in_expert(ids_flat, ep):
    """Slot index of each assignment within its expert's capacity bucket."""
    oh = jax.nn.one_hot(ids_flat, ep, dtype=jnp.int32)  # (A, E)
    pos = jnp.cumsum(oh, axis=0) * oh  # 1-based where selected
    return jnp.sum(pos, axis=1) - 1  # (A,) 0-based


def _dispatch_local(params, xt, cfg: ArchConfig, capacity: int):
    """Route local tokens xt (t, D) into a capacity buffer (E_pad, C, D)."""
    m = cfg.moe
    ep = _epad(cfg)
    t, d = xt.shape
    w, ids, probs = _router(params, xt, cfg)
    ids_flat = ids.reshape(-1)  # (t·k,)
    pos = _positions_in_expert(ids_flat, ep)
    tok_idx = jnp.repeat(jnp.arange(t), m.top_k)
    buf = jnp.zeros((ep, capacity, d), xt.dtype)
    buf = buf.at[ids_flat, pos].set(xt[tok_idx], mode="drop")
    return buf, (w, ids_flat, pos, tok_idx), (probs, ids)


def _combine_local(buf_out, route, t, d, dtype):
    w, ids_flat, pos, tok_idx = route
    y_k = buf_out.at[ids_flat, pos].get(mode="fill", fill_value=0)  # (t·k, D)
    contrib = y_k.astype(jnp.float32) * w.reshape(-1)[:, None]
    y = jnp.zeros((t, d), jnp.float32)
    return y.at[tok_idx].add(contrib).astype(dtype)


def _a2a_to_experts(buf, ep_axes):
    """(E_pad, C, D) per device → (E_loc, C·n_ep, D) on each expert's owner.

    One all_to_all hop per expert-sharding mesh axis: split the expert axis,
    concatenate received contributions along the capacity axis (source-rank
    major) — the concat order is undone exactly by ``_a2a_from_experts``.
    """
    for ax in ep_axes:
        buf = jax.lax.all_to_all(buf, ax, split_axis=0, concat_axis=1, tiled=True)
    return buf


def _a2a_from_experts(buf, ep_axes):
    for ax in reversed(ep_axes):
        buf = jax.lax.all_to_all(buf, ax, split_axis=1, concat_axis=0, tiled=True)
    return buf


def _ep_rank(ep_axes, mesh):
    idx = 0
    for ax in ep_axes:
        idx = idx * mesh.shape[ax] + jax.lax.axis_index(ax)
    return idx


def _moe_sharded_body(params, x, cfg: ArchConfig, mesh, ep_axes, mode, tp_split):
    """Per-device body. x: (B_l, S, D) local shard."""
    m = cfg.moe
    ep = _epad(cfg)
    b_l, s, d = x.shape
    t_all = b_l * s
    xf = x.reshape(t_all, d)
    n_ep = math.prod([mesh.shape[a] for a in ep_axes]) if ep_axes else 1
    e_loc = ep // n_ep

    if mode == "gather":
        # Few tokens: replicate them across the EP axes that shard tokens,
        # compute the local expert shard for all of them, psum-combine.
        dp = batch_axes(mesh)
        gather_axes = tuple(a for a in ep_axes if a in dp)
        xg = xf
        for ax in gather_axes:
            xg = jax.lax.all_gather(xg, ax, axis=0, tiled=True)
        tg = xg.shape[0]
        w, ids, probs = _router(params, xg, cfg)
        h = _expert_ffn(
            params["wg"], params["wu"], params["wd"],
            jnp.broadcast_to(xg[None], (e_loc, tg, d)), cfg,
        )
        gates = jnp.zeros((tg, ep), jnp.float32)
        gates = gates.at[jnp.arange(tg)[:, None], ids].set(w)
        e_start = _ep_rank(ep_axes, mesh) * e_loc if ep_axes else 0
        g_loc = jax.lax.dynamic_slice_in_dim(gates, e_start, e_loc, axis=1)
        y = jnp.einsum("te,etd->td", g_loc.astype(x.dtype), h)
        if ep_axes:
            y = jax.lax.psum(y, ep_axes)
        # slice own token block back out (inverse of the all_gathers)
        for ax in reversed(gather_axes):
            n = mesh.shape[ax]
            blk = y.shape[0] // n
            y = jax.lax.dynamic_slice_in_dim(y, jax.lax.axis_index(ax) * blk, blk, axis=0)
        aux = _aux_loss(probs, ids, cfg)
    else:  # a2a
        r = jax.lax.axis_index("model") if tp_split > 1 else 0
        t = t_all // tp_split
        xt = jax.lax.dynamic_slice_in_dim(xf, r * t, t, axis=0)
        capacity = max(1, int(math.ceil(t * m.top_k / m.num_experts * m.capacity_factor)))
        buf, route, (probs, ids) = _dispatch_local(params, xt, cfg, capacity)
        buf = _a2a_to_experts(buf, ep_axes)  # (e_loc, C·n_ep, D)
        h = _expert_ffn(params["wg"], params["wu"], params["wd"], buf, cfg)
        buf_out = _a2a_from_experts(h, ep_axes)  # (E_pad, C, D)
        y = _combine_local(buf_out, route, t, d, x.dtype)
        if tp_split > 1:
            y = jax.lax.all_gather(y, "model", axis=0, tiled=True)  # (t_all, D)
        aux = _aux_loss(probs, ids, cfg)

    y = y.reshape(b_l, s, d)
    if m.num_shared:
        y = y + _shared_ffn(params["shared"], x, cfg)
    denom = math.prod([v for v in mesh.shape.values()])
    aux = jax.lax.psum(aux, tuple(mesh.axis_names)) / denom
    return y, aux


def moe_apply(params, x, cfg: ArchConfig):
    """Returns (y, aux_loss). Picks dense / gather / a2a automatically."""
    mesh = active_mesh()
    m = cfg.moe
    if (mesh is None or math.prod([v for v in mesh.shape.values()]) == 1
            or m.held < _epad(cfg)):  # a share runs its own experts alone
        y, aux = _moe_dense(params, x, cfg)
        if m.num_shared:
            y = y + _shared_ffn(params["shared"], x, cfg)
        return y, aux

    ep = _epad(cfg)
    # expert-sharding axes actually available on this mesh
    ep_axes = tuple(a for a in m.ep_axes if a in mesh.shape and mesh.shape[a] > 1)
    n_ep = math.prod([mesh.shape[a] for a in ep_axes]) if ep_axes else 1
    while ep_axes and ep % n_ep != 0:
        ep_axes = ep_axes[1:]
        n_ep = math.prod([mesh.shape[a] for a in ep_axes]) if ep_axes else 1

    dp = batch_axes(mesh)
    b, s, d = x.shape
    dp_size = math.prod([mesh.shape[a] for a in dp])
    shard_batch = dp_size > 1 and b % dp_size == 0
    b_l = b // dp_size if shard_batch else b
    x_spec = P(dp if len(dp) > 1 else dp[0], None, None) if shard_batch else P(None, None, None)
    t_all = b_l * s
    tp = mesh.shape.get("model", 1)
    if "model" in dp:  # fsdp_only: tokens already sharded over "model" as DP
        tp = 1
    tp_split = tp if (t_all % tp == 0 and t_all // tp >= 64) else 1
    t = t_all // tp_split
    mode = "a2a" if (ep_axes and t >= 64 and t * m.top_k >= 2 * m.num_experts) else "gather"

    pspec = {
        "router": P(None, None),
        "wg": _e_spec(ep_axes), "wu": _e_spec(ep_axes), "wd": _e_spec(ep_axes),
    }
    if m.num_shared:  # shared expert weights are small → replicate
        pspec["shared"] = {"wg": P(None, None), "wu": P(None, None), "wd": P(None, None)}

    fn = partial(_moe_sharded_body, cfg=cfg, mesh=mesh, ep_axes=ep_axes,
                 mode=mode, tp_split=tp_split)
    y, aux = jax.shard_map(
        fn, mesh=mesh,
        in_specs=(pspec, x_spec),
        out_specs=(x_spec, P()),
        check_vma=False,
    )(params, x)
    return y, aux


def _e_spec(ep_axes):
    if not ep_axes:
        return P(None, None, None)
    return P(ep_axes if len(ep_axes) > 1 else ep_axes[0], None, None)
