"""Mixture-of-Experts: top-k routing with sort-based capacity dispatch.

Two dispatch paths:

* ``moe_block`` (portable): argsort tokens by expert, scatter into an
  (E, C, d) buffer. Compiles everywhere but the data-dependent scatter makes
  the SPMD partitioner re-replicate the buffer — measured at ~169 TB/device
  of all-reduce for deepseek-v3 train_4k (EXPERIMENTS.md §Perf baseline).
* ``moe_block_ep`` (production): explicit expert parallelism via shard_map
  over the ``model`` axis. Activations are replicated across ``model`` under
  our layout, so each device routes its *local* tokens to its *local*
  E/|model| experts with a purely local sort/scatter, and expert outputs are
  combined with one psum — wire cost drops from O(E*C*d) scatter resharding
  to exactly one (T_local, d) all-reduce per MoE layer. Used automatically
  when a mesh with a ``model`` axis is active.

Shared experts (deepseek) run densely on every token.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P
from jax import shard_map

from repro.core import abft

from . import layers
from .layers import dense_init

__all__ = ["make_moe_params", "moe_block", "moe_block_ep",
           "aux_load_balance_loss"]


def _ft_expert_matmul(buf, w, threshold, correct):
    """Per-expert checked GEMMs: vmap the two-side ABFT matmul over the
    expert axis. buf: (e, c, d) @ w: (e, d, f) -> ((e, c, f), stats with
    (e,) leaves). Batched weights are per-expert plans, so this rides the
    XLA interpreter path directly (the fused kernel takes one weight)."""
    return jax.vmap(lambda b2, w2: abft.ft_matmul(
        b2, w2, threshold=threshold, with_correction=correct))(buf, w)


def _merge_expert_stats(*stats_dicts):
    """Sum the count leaves / max the score across the three expert GEMMs
    (leaves stay (e,) vectors; FTContext.summary reduces them)."""
    out = {}
    for k in stats_dicts[0]:
        vals = [s[k] for s in stats_dicts]
        out[k] = (functools.reduce(jnp.maximum, vals) if k == "score"
                  else sum(vals))
    return out


def make_moe_params(key, cfg, dtype=jnp.float32):
    d, e, f = cfg.d_model, cfg.num_experts, cfg.moe_d_ff
    ks = jax.random.split(key, 5)
    p = {
        "router": dense_init(ks[0], (d, e), jnp.float32),
        "wi_gate": dense_init(ks[1], (e, d, f), dtype, ),
        "wi_up": dense_init(ks[2], (e, d, f), dtype),
        "wo": dense_init(ks[3], (e, f, d), dtype),
    }
    if cfg.num_shared_experts:
        p["shared"] = layers.make_mlp_params(
            ks[4], d, cfg.moe_d_ff * cfg.num_shared_experts, "swiglu", dtype)
    return p


def _dispatch_compute(xf, gate_vals, gate_idx, wg, wu, wo, cap, e, *,
                      dtype, ft_args=None):
    """Sort-based capacity dispatch + expert FFN + combine (local arrays).

    xf: (T, d); gate_idx/vals: (T, k); wg/wu: (e, d, f); wo: (e, f, d).
    Expert ids in gate_idx are in [0, e) (caller rebases for EP shards;
    out-of-range ids are dropped by the capacity mask).

    ``ft_args = (threshold, correct)`` routes the three expert GEMMs
    through the two-side ABFT; returns ``(y, stats)`` with stats ``None``
    when unprotected.
    """
    t, d = xf.shape
    k = gate_idx.shape[-1]
    flat_e = jnp.clip(gate_idx.reshape(-1), 0, e)        # e == drop bucket
    valid = gate_idx.reshape(-1) == flat_e
    flat_e = jnp.where(valid, flat_e, e)
    order = jnp.argsort(flat_e)
    sorted_e = flat_e[order]
    first = jnp.searchsorted(sorted_e, jnp.arange(e), side="left")
    pos_in_e = jnp.arange(t * k) - first[jnp.clip(sorted_e, 0, e - 1)]
    keep = (pos_in_e < cap) & (sorted_e < e)
    dest = jnp.where(keep, sorted_e * cap + pos_in_e, e * cap)
    src_token = order // k

    buf = jnp.zeros((e * cap + 1, d), dtype)
    buf = buf.at[dest].set(xf.astype(dtype)[src_token], mode="drop")
    buf = buf[:-1].reshape(e, cap, d)

    if ft_args is not None:
        threshold, correct = ft_args
        gate, s1 = _ft_expert_matmul(buf, wg, threshold, correct)
        up, s2 = _ft_expert_matmul(buf, wu, threshold, correct)
        act = jax.nn.silu(gate) * up
        out_buf, s3 = _ft_expert_matmul(act, wo, threshold, correct)
        stats = _merge_expert_stats(s1, s2, s3)
    else:
        gate = jnp.einsum("ecd,edf->ecf", buf, wg.astype(dtype))
        up = jnp.einsum("ecd,edf->ecf", buf, wu.astype(dtype))
        act = jax.nn.silu(gate) * up
        out_buf = jnp.einsum("ecf,efd->ecd", act, wo.astype(dtype))
        stats = None

    out_flat = out_buf.reshape(e * cap, d)
    gathered = jnp.where(keep[:, None],
                         out_flat[jnp.clip(dest, 0, e * cap - 1)], 0.0)
    unsort = jnp.argsort(order)
    contrib = gathered[unsort].reshape(t, k, d)
    return jnp.einsum("tkd,tk->td", contrib, gate_vals.astype(dtype)), stats


def moe_block_ep(params, x, cfg, mesh, *, ft=None):
    """Expert-parallel MoE: shard_map over the ``model`` axis.

    Each device handles E/|model| experts for its local tokens; combine is
    one psum. Router runs replicated (it is O(T*E), negligible).
    """
    b, t, d = x.shape
    e, k = cfg.num_experts, cfg.top_k
    m_size = mesh.shape["model"]
    e_local = e // m_size
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    # per-device tokens after dp sharding of the batch; small decode batches
    # (long_500k: B=1) replicate over dp instead
    import numpy as _np
    dp_size = int(_np.prod([mesh.shape[a] for a in dp])) if dp else 1
    if b % max(dp_size, 1):
        dp = ()
        dp_size = 1
    tokens_local = max(b // max(dp_size, 1), 1) * t
    cap = max(int(np.ceil(tokens_local * k / e * cfg.capacity_factor)), 8)

    # traced arrays cannot escape the shard_map closure via FTContext.record
    # — when protected, the local stats come back as extra psum'd outputs
    # (only then, so the unprotected path's collective count is unchanged)
    ft_on = ft is not None and ft.enabled
    ft_args = ((ft.policy.threshold, True) if ft_on else None)

    def local_fn(xb, router_w, wg, wu, wo):
        # xb: (B_loc, T, d) — replicated over model; wg: (e_local, d, f)
        bl = xb.shape[0]
        xf = xb.reshape(bl * t, d)
        logits = jnp.einsum("td,de->te", xf.astype(jnp.float32), router_w)
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, gate_idx = jax.lax.top_k(probs, k)
        gate_vals = gate_vals / (jnp.sum(gate_vals, -1, keepdims=True) + 1e-9)
        # rebase expert ids to this shard's local range
        m_idx = jax.lax.axis_index("model")
        local_idx = gate_idx - m_idx * e_local
        local_idx = jnp.where((local_idx >= 0) & (local_idx < e_local),
                              local_idx, e_local)  # -> drop bucket
        y, stats = _dispatch_compute(xf, gate_vals, local_idx, wg, wu, wo,
                                     cap, e_local, dtype=x.dtype,
                                     ft_args=ft_args)
        y = jax.lax.psum(y, "model")  # combine expert shards
        aux = aux_load_balance_loss(probs, gate_idx, e)
        if dp:
            aux = jax.lax.pmean(aux, dp)  # global mean over token shards
        if stats is None:
            return y.reshape(bl, t, d), aux
        axes = ("model",) + dp  # replicate stats across every shard
        return (y.reshape(bl, t, d), aux,
                jax.lax.psum(jnp.sum(stats["flagged"]), axes),
                jax.lax.psum(jnp.sum(stats["corrected"]), axes),
                jax.lax.pmax(jnp.max(stats["score"]), axes))

    in_specs = (P(dp if dp else None, None, None),   # x: batch over dp
                P(None, None),                        # router replicated
                P("model", None, None), P("model", None, None),
                P("model", None, None))
    out_specs = (P(dp if dp else None, None, None), P())
    if ft_on:
        out_specs = out_specs + (P(), P(), P())
    fn = shard_map(local_fn, mesh=mesh, in_specs=in_specs,
                   out_specs=out_specs, check_vma=False)
    out = fn(x, params["router"], params["wi_gate"], params["wi_up"],
             params["wo"])
    y, aux = out[0], out[1]
    if ft_on:
        ft.record({"flagged": out[2], "corrected": out[3], "score": out[4]})
    if "shared" in params:
        y = y + layers.swiglu(params["shared"], x.reshape(b * t, d),
                              ft=ft).reshape(b, t, d)
    return y, aux


def moe_block(params, x, cfg, *, ft=None):
    """x: (B, T, D) -> (y, aux) with capacity-based top-k dispatch.

    Dispatch-path selection (measured, EXPERIMENTS.md §Perf cell 1):
    * explicit EP (shard_map) when a production mesh is active AND the
      per-device token count is large (train/prefill) — the psum combine is
      ~1000x cheaper than the scatter resharding the partitioner emits;
    * portable scatter path for tiny decode steps (~8 tokens/device), where
      EP's replicated routing + per-layer psum costs more than it saves.
    """
    from repro.parallel.sharding import current_mesh, dp_axes
    mesh = current_mesh()
    if mesh is not None and "model" in mesh.axis_names and \
            cfg.num_experts % mesh.shape["model"] == 0:
        b, t, _ = x.shape
        dp = int(np.prod([mesh.shape[a] for a in dp_axes(mesh)])) or 1
        tokens_local = (b // dp if b % dp == 0 else b) * t
        if tokens_local >= 1024:
            return moe_block_ep(params, x, cfg, mesh, ft=ft)
    return _moe_block_portable(params, x, cfg, ft=ft)


def _moe_block_portable(params, x, cfg, *, ft=None):
    """x: (B, T, D) -> (y, aux) with capacity-based top-k dispatch."""
    b, t, d = x.shape
    e, k = cfg.num_experts, cfg.top_k
    tokens = b * t
    cap = int(np.ceil(tokens * k / e * cfg.capacity_factor))
    cap = max(cap, 8)

    xf = x.reshape(tokens, d)
    logits = jnp.einsum("td,de->te", xf.astype(jnp.float32),
                        params["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, k)        # (T, k)
    gate_vals = gate_vals / (jnp.sum(gate_vals, axis=-1, keepdims=True)
                             + 1e-9)

    # ---- sort-based dispatch ------------------------------------------------
    flat_e = gate_idx.reshape(-1)                         # (T*k,)
    order = jnp.argsort(flat_e)                           # stable
    sorted_e = flat_e[order]
    # position of each entry within its expert
    first = jnp.searchsorted(sorted_e, jnp.arange(e), side="left")
    pos_in_e = jnp.arange(tokens * k) - first[sorted_e]
    keep = pos_in_e < cap
    dest = jnp.where(keep, sorted_e * cap + pos_in_e, e * cap)  # drop slot
    src_token = order // k

    buf = jnp.zeros((e * cap + 1, d), x.dtype)
    buf = buf.at[dest].set(xf[src_token], mode="drop")
    buf = buf[:-1].reshape(e, cap, d)
    from repro.parallel.sharding import constrain_moe_buffer
    buf = constrain_moe_buffer(buf)

    # ---- expert FFN (EP: the leading E axis is sharded over `tensor`) ------
    if ft is not None and ft.enabled:
        thr = ft.policy.threshold
        gate, s1 = _ft_expert_matmul(buf, params["wi_gate"], thr, True)
        up, s2 = _ft_expert_matmul(buf, params["wi_up"], thr, True)
        act = jax.nn.silu(gate) * up
        out_buf, s3 = _ft_expert_matmul(act, params["wo"], thr, True)
        ft.record(_merge_expert_stats(s1, s2, s3))
    else:
        gate = jnp.einsum("ecd,edf->ecf", buf,
                          params["wi_gate"].astype(x.dtype))
        up = jnp.einsum("ecd,edf->ecf", buf, params["wi_up"].astype(x.dtype))
        act = jax.nn.silu(gate) * up
        out_buf = jnp.einsum("ecf,efd->ecd", act,
                             params["wo"].astype(x.dtype))

    # ---- combine ------------------------------------------------------------
    out_flat = out_buf.reshape(e * cap, d)
    gathered = jnp.where(keep[:, None],
                         out_flat[jnp.clip(dest, 0, e * cap - 1)], 0.0)
    # unsort back to (T*k) order, weight by gates, sum over k
    unsort = jnp.argsort(order)
    contrib = gathered[unsort].reshape(tokens, k, d)
    y = jnp.einsum("tkd,tk->td", contrib, gate_vals.astype(x.dtype))

    if "shared" in params:
        y = y + layers.swiglu(params["shared"], xf, ft=ft)

    aux = aux_load_balance_loss(probs, gate_idx, e)
    return y.reshape(b, t, d), aux


def aux_load_balance_loss(probs, gate_idx, e):
    """Switch-style load-balance auxiliary loss."""
    t = probs.shape[0]
    density = jnp.zeros((e,), jnp.float32).at[gate_idx.reshape(-1)].add(1.0)
    density = density / jnp.maximum(jnp.sum(density), 1.0)
    router_prob = jnp.mean(probs, axis=0)
    return e * jnp.sum(density * router_prob)
