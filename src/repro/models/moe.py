"""Mixture-of-Experts with an explicit shard_map schedule.

Unified capacity-buffer dispatch (GShard-style dropping), two weight
layouts chosen automatically by divisibility against the ``model`` axis:

  * EP  (n_experts % model_size == 0, e.g. deepseek 256, jamba 16):
    experts sharded over ``model``; every model-shard holds the full
    (replicated) activations, dispatches only the tokens routed to its
    local experts into an (E_local, C, D) buffer, runs dense per-expert
    matmuls (MXU-shaped), and the partial outputs are psum'd over
    ``model``.  Compute per shard = 1/model_size of the MoE FLOPs; the
    only collective is the same (T, D) psum a tensor-parallel MLP pays.

  * TP  (small expert counts, e.g. mixtral 8): all experts local, the
    d_expert dim sharded over ``model``; same buffer, same psum.

Outside a mesh (CPU tests) the same local function runs unsharded.

The router aux (Switch load-balance loss) is pmean'd across shards.
ep_mode="a2a" (hillclimb target) replaces the replicated-activation
dispatch with a true all-to-all token exchange — see §Perf.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.config import ArchConfig
from repro.models import layers as L
from repro.models.layers import PAb


def moe_ab(cfg: ArchConfig):
    d = cfg.d_model
    m = cfg.moe
    s = d ** -0.5
    p = {
        "router": PAb((d, m.n_experts), ("embed", None), "normal", s),
        "up": PAb((m.n_experts, d, m.d_expert), ("experts", "embed", "mlp"),
                  "normal", s),
        "gate": PAb((m.n_experts, d, m.d_expert), ("experts", "embed", "mlp"),
                    "normal", s),
        "down": PAb((m.n_experts, m.d_expert, d), ("experts", "mlp", "embed"),
                    "normal", m.d_expert ** -0.5),
    }
    if m.n_shared:
        p["shared"] = L.mlp_ab(d, m.d_expert * m.n_shared, gated=cfg.gated)
    return p


def _capacity(cfg, T):
    m = cfg.moe
    return max(int(math.ceil(T * m.top_k * m.capacity_factor / m.n_experts)),
               min(8, T))


def _router(cfg, router_w, x):
    """x: (T, D) -> (weights (T,k), ids (T,k), aux_loss)."""
    m = cfg.moe
    logits = (x @ router_w.astype(x.dtype)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    weights, ids = jax.lax.top_k(probs, m.top_k)
    if m.router_scale:
        weights = weights / jnp.maximum(
            jnp.sum(weights, -1, keepdims=True), 1e-9)
    T = x.shape[0]
    f = jnp.zeros(m.n_experts, jnp.float32).at[ids.reshape(-1)].add(1.0) \
        / (T * m.top_k)
    pbar = jnp.mean(probs, axis=0).astype(jnp.float32)
    aux = (m.n_experts * jnp.sum(f * pbar)).astype(jnp.float32)
    return weights.astype(x.dtype), ids, aux


def _dispatch_indices(cfg, ids, T, C, e_start, e_count):
    """Slot bookkeeping for the capacity buffer of local experts
    [e_start, e_start+e_count).  Returns (tok_idx, local_eid, slot, keep)
    all shaped (T*top_k,)."""
    m = cfg.moe
    flat_ids = ids.reshape(-1)                        # (T*k,) global expert
    local = jnp.logical_and(flat_ids >= e_start, flat_ids < e_start + e_count)
    local_eid = jnp.where(local, flat_ids - e_start, e_count)  # e_count=trash
    # position within each expert's queue, computed in (token,slot) order
    onehot = jax.nn.one_hot(local_eid, e_count + 1, dtype=jnp.int32)
    pos = jnp.cumsum(onehot, axis=0) - 1                       # (T*k, E+1)
    slot = jnp.take_along_axis(pos, local_eid[:, None], axis=1)[:, 0]
    keep = jnp.logical_and(local, slot < C)
    tok_idx = jnp.arange(flat_ids.shape[0]) // m.top_k
    return tok_idx, local_eid, slot, keep


def _expert_ffn(cfg, up, gate, down, xe):
    """xe: (E_loc, C, D) -> (E_loc, C, D); dense per-expert matmuls."""
    actf = jax.nn.silu if cfg.act == "silu" else (
        lambda z: jax.nn.gelu(z, approximate=True))
    h = jnp.einsum("ecd,edf->ecf", xe, up.astype(xe.dtype))
    if cfg.gated:
        h = actf(jnp.einsum("ecd,edf->ecf", xe, gate.astype(xe.dtype))) * h
    else:
        h = actf(h)
    return jnp.einsum("ecf,efd->ecd", h, down.astype(xe.dtype))


def _local_moe(cfg, x, router_w, up, gate, down, e_start, n_local, C,
               model_axis=None, batch_axes=()):
    """Per-shard MoE: x (T,D) local tokens, experts [e_start, +n_local)."""
    T, D = x.shape
    weights, ids, aux = _router(cfg, router_w, x)
    tok_idx, local_eid, slot, keep = _dispatch_indices(
        cfg, ids, T, C, e_start, n_local)

    safe_e = jnp.minimum(local_eid, n_local - 1)
    safe_s = jnp.minimum(slot, C - 1)
    xe = jnp.zeros((n_local, C, D), x.dtype)
    gathered = x[tok_idx] * keep[:, None].astype(x.dtype)
    xe = xe.at[safe_e, safe_s].add(jnp.where(keep[:, None], gathered, 0.0))

    ye = _expert_ffn(cfg, up, gate, down, xe)

    w_flat = weights.reshape(-1)
    contrib = ye[safe_e, safe_s] * (w_flat * keep.astype(w_flat.dtype))[:, None]
    y = jnp.zeros_like(x).at[tok_idx].add(contrib)

    if model_axis is not None:
        y = jax.lax.psum(y, model_axis)
    for ax in batch_axes:
        aux = jax.lax.pmean(aux, ax)
    if model_axis is not None:
        aux = jax.lax.pmean(aux, model_axis)
    return y, aux


def moe_block(cfg: ArchConfig, params, x, mesh=None
              ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: (B,S,D) -> (B,S,D)."""
    m = cfg.moe
    B, S, D = x.shape

    if mesh is None or "model" not in mesh.axis_names:
        xt = x.reshape(B * S, D)
        C = _capacity(cfg, B * S)
        y, aux = _local_moe(cfg, xt, params["router"], params["up"],
                            params["gate"], params["down"],
                            e_start=0, n_local=m.n_experts, C=C)
        if m.n_shared:
            y = y + L.mlp(params["shared"], xt, cfg.act, cfg.gated)
        return y.reshape(B, S, D), aux

    model_n = mesh.shape["model"]
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    batch_div = math.prod(mesh.shape[a] for a in batch_axes) or 1
    div_ok = B % batch_div == 0
    if not div_ok:      # e.g. batch=1 long-context decode: replicate
        batch_axes = ()
    B_local = B // batch_div if div_ok else B
    T_local = B_local * S
    ep = m.n_experts % model_n == 0 and m.n_experts >= model_n
    n_local = m.n_experts // model_n if ep else m.n_experts
    C = _capacity(cfg, T_local)

    # a2a-EP (§Perf E3b): with the residual stream sequence-sharded over
    # ``model``, dispatch routed token copies to their expert's shard by
    # all_to_all instead of replicating x and psumming partial outputs.
    # Wire per layer drops from AG(x)+AR(y) [~3x activation bytes] to
    # 2 x routed-copy bytes; no collective touches unrouted tokens.
    if ep and S % model_n == 0 and S > 1:
        return _a2a_moe_block(cfg, params, x, mesh, model_n, batch_axes,
                              B_local, n_local)

    batch_p = batch_axes if len(batch_axes) > 1 else (
        batch_axes[0] if batch_axes else None)
    x_spec = P(batch_p, None, None)
    if ep:
        w_spec = P("model", None, None)
    else:
        w_spec = P(None, None, "model")
    down_spec = P("model", None, None) if ep else P(None, "model", None)

    # shared experts ride inside the shard region, tensor-sharded on
    # d_expert, so their partial output folds into the SAME psum as the
    # routed experts (§Perf E3a: one collective per MoE layer, not two)
    shared = params.get("shared")

    def shard_fn(x_l, router_w, up, gate, down, *shared_w):
        T = x_l.shape[0] * x_l.shape[1]
        xt = x_l.reshape(T, D)
        if ep:
            e_start = jax.lax.axis_index("model") * n_local
        else:
            e_start = 0
        y, aux = _local_moe(cfg, xt, router_w, up, gate, down,
                            e_start=e_start, n_local=n_local, C=C,
                            model_axis=None, batch_axes=batch_axes)
        if shared_w:
            sp = dict(zip(sorted(shared), shared_w))
            y = y + L.mlp(sp, xt, cfg.act, cfg.gated)
        y = jax.lax.psum(y, "model")
        return y.reshape(x_l.shape), jax.lax.pmean(aux, "model")

    shared_args, shared_specs = (), ()
    if shared is not None:
        names = sorted(shared)          # down, gate?, up
        shared_args = tuple(shared[k] for k in names)
        shared_specs = tuple(P("model", None) if k == "down"
                             else P(None, "model") for k in names)
    fn = jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(x_spec, P(None, None), w_spec, w_spec, down_spec)
        + shared_specs,
        out_specs=(x_spec, P()),
        check_vma=False)
    y, aux = fn(x, params["router"], params["up"], params["gate"],
                params["down"], *shared_args)
    return y, aux


def _a2a_moe_block(cfg, params, x, mesh, model_n, batch_axes, B_local,
                   n_local):
    """Expert parallelism with all_to_all dispatch over seq-sharded x.

    Per shard: T = B_local * S/model_n local tokens.  Stage 1 buckets
    each (token, slot) by destination shard (cap_out per peer); a2a
    ships the buckets.  Stage 2 buckets arrivals by local expert
    (capacity C2), runs the dense per-expert FFN, and the results take
    the reverse trip.  The shared expert (deepseek) runs locally on the
    seq shard with replicated weights — zero collectives."""
    m = cfg.moe
    B, S, D = x.shape
    T = B_local * (S // model_n)
    cap_out = max(int(math.ceil(T * m.top_k * m.capacity_factor / model_n)),
                  min(8, T * m.top_k))
    C2 = max(int(math.ceil(cap_out * model_n * m.capacity_factor
                           * 1.0 / n_local)), 8)

    batch_p = batch_axes if len(batch_axes) > 1 else (
        batch_axes[0] if batch_axes else None)
    x_spec = P(batch_p, "model", None)
    w_spec = P("model", None, None)

    shared = params.get("shared")
    shared_names = sorted(shared) if shared is not None else []
    shared_args = tuple(shared[k] for k in shared_names)
    shared_specs = tuple(P(None, None) for _ in shared_names)

    def shard_fn(x_l, router_w, up, gate, down, *shared_w):
        xt = x_l.reshape(T, D)
        weights, ids, aux = _router(cfg, router_w, xt)

        # ---- stage 1: bucket by destination shard
        flat_ids = ids.reshape(-1)                     # (T*k,) global expert
        dest = flat_ids // n_local                     # destination shard
        onehot = jax.nn.one_hot(dest, model_n, dtype=jnp.int32)
        pos = jnp.cumsum(onehot, axis=0) - 1
        slot = jnp.take_along_axis(pos, dest[:, None], axis=1)[:, 0]
        keep = slot < cap_out
        tok_idx = jnp.arange(flat_ids.shape[0]) // m.top_k
        sd = jnp.minimum(slot, cap_out - 1)

        send = jnp.zeros((model_n, cap_out, D), xt.dtype)
        send = send.at[dest, sd].add(
            jnp.where(keep[:, None], xt[tok_idx], 0.0))
        # metadata: local expert id (+1, 0 = empty) rides along
        meta = jnp.zeros((model_n, cap_out), jnp.int32)
        meta = meta.at[dest, sd].max(
            jnp.where(keep, (flat_ids % n_local) + 1, 0))

        recv = jax.lax.all_to_all(send, "model", 0, 0, tiled=False)
        meta_r = jax.lax.all_to_all(meta[..., None], "model", 0, 0,
                                    tiled=False)[..., 0]

        # ---- stage 2: bucket arrivals by local expert
        arr = recv.reshape(model_n * cap_out, D)
        eid = meta_r.reshape(-1)                       # 0 = empty slot
        e1 = jnp.where(eid > 0, eid - 1, n_local)      # trash lane n_local
        oh2 = jax.nn.one_hot(e1, n_local + 1, dtype=jnp.int32)
        pos2 = jnp.cumsum(oh2, axis=0) - 1
        slot2 = jnp.take_along_axis(pos2, e1[:, None], axis=1)[:, 0]
        keep2 = jnp.logical_and(eid > 0, slot2 < C2)
        se = jnp.minimum(e1, n_local - 1)
        ss = jnp.minimum(slot2, C2 - 1)
        xe = jnp.zeros((n_local, C2, D), xt.dtype)
        xe = xe.at[se, ss].add(jnp.where(keep2[:, None], arr, 0.0))

        ye = _expert_ffn(cfg, up, gate, down, xe)

        back = jnp.where(keep2[:, None], ye[se, ss], 0.0) \
            .reshape(model_n, cap_out, D)
        ret = jax.lax.all_to_all(back, "model", 0, 0, tiled=False)

        # ---- combine on the source shard
        w_flat = weights.reshape(-1)
        contrib = ret[dest, sd] * (
            w_flat * keep.astype(w_flat.dtype))[:, None]
        y = jnp.zeros_like(xt).at[tok_idx].add(contrib)

        if shared_w:
            sp = dict(zip(shared_names, shared_w))
            y = y + L.mlp(sp, xt, cfg.act, cfg.gated)
        for ax in batch_axes:
            aux = jax.lax.pmean(aux, ax)
        aux = jax.lax.pmean(aux, "model")
        return y.reshape(x_l.shape), aux

    fn = jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(x_spec, P(None, None), w_spec, w_spec, w_spec)
        + shared_specs,
        out_specs=(x_spec, P()),
        check_vma=False)
    return fn(x, params["router"], params["up"], params["gate"],
              params["down"], *shared_args)
