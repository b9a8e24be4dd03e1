"""Mixture-of-Experts channel mixer.

Baseline impl ("dispatch"): sort-based capacity dispatch in pure pjit-friendly
jnp — top-k routing, per-expert rank via stable sort, scatter into (E, C, d)
expert buffers, batched expert matmuls with the expert axis sharded over
"model" (expert parallelism), gather/combine back. Tokens past capacity are
dropped (GShard semantics); aux load-balancing loss returned for training.

The all-to-all pattern between the token-sharded and expert-sharded layouts
is left to XLA SPMD here — that choice is deliberate: it is the baseline the
§Perf hillclimb measures against (a shard_map variant with explicit
all_to_all is the optimized path).

Routing flavours:
  softmax top-k, renormalised (phi3.5-moe, jamba)      — experts_per_token=2
  sigmoid top-1 + shared expert (llama4-maverick)      — experts_per_token=1
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.models.layers import Builder, Sharder
from repro.models.mlp import init_mlp, mlp_apply

Array = jax.Array


def init_moe(b: Builder, cfg) -> dict:
    d, f, e = cfg.d_model, cfg.d_ff, cfg.num_experts
    p = {
        # router replicated: tiny, and the all-to-all path routes locally
        "router": b.make((d, e), (None, None), init="normal", scale=0.02),
        "w_gate": b.make((e, d, f), ("experts", "embed", "mlp")),
        "w_up": b.make((e, d, f), ("experts", "embed", "mlp")),
        "w_down": b.make((e, f, d), ("experts", "mlp", "embed")),
    }
    if getattr(cfg, "moe_shared_experts", 0) or cfg.name.startswith("llama4"):
        p["shared"] = init_mlp(b, cfg)
    return p


def _route(p: dict, xt: Array, cfg) -> Tuple[Array, Array, Array]:
    """xt: (T, d) -> (gates (T,k), idx (T,k), aux_loss scalar)."""
    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32), p["router"].astype(jnp.float32))
    k = cfg.experts_per_token
    if k == 1 and "shared" in p:  # llama4: sigmoid gate on the top-1 expert
        top_val, top_idx = jax.lax.top_k(logits, 1)
        gates = jax.nn.sigmoid(top_val)
        probs = jax.nn.softmax(logits, axis=-1)
    else:
        probs = jax.nn.softmax(logits, axis=-1)
        gates, top_idx = jax.lax.top_k(probs, k)
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    # Switch-style load balancing aux loss
    e = cfg.num_experts
    me = jnp.mean(probs, axis=0)  # (E,)
    ce = jnp.mean(
        (jax.nn.one_hot(top_idx[:, 0], e, dtype=jnp.float32)), axis=0
    )
    aux = e * jnp.sum(me * ce)
    return gates, top_idx, aux


def moe_apply(p: dict, x: Array, cfg, shd: Sharder) -> Tuple[Array, Array]:
    """x: (B,S,d) -> (y, aux_loss). Dispatches on cfg.moe_impl."""
    if cfg.moe_impl == "alltoall" and shd.mesh is not None:
        tp = shd.mesh.shape.get("model", 1)
        b_, s, _ = x.shape
        dp = 1
        for ax in ("pod", "data"):
            dp *= shd.mesh.shape.get(ax, 1)
        t_loc = (b_ // dp) * s if b_ % dp == 0 else 0
        if tp > 1 and t_loc % tp == 0:
            return moe_apply_alltoall(p, x, cfg, shd)
    return moe_apply_dispatch(p, x, cfg, shd)


def moe_apply_dispatch(p: dict, x: Array, cfg, shd: Sharder) -> Tuple[Array, Array]:
    """Baseline: sort+scatter capacity dispatch, collectives left to XLA SPMD."""
    b_, s, d = x.shape
    t = b_ * s
    k = cfg.experts_per_token
    e = cfg.num_experts
    xt = x.reshape(t, d)
    gates, idx, aux = _route(p, xt, cfg)

    # capacity per expert: cf x the mean load, floored at 8 slots so tiny
    # decode batches keep headroom (serve configs raise cf for dropless-ness)
    cap = max(-(-int(cfg.moe_capacity_factor * t * k) // e), 8)

    flat_e = idx.reshape(-1)  # (T*k,) expert id per (token, slot)
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    # rank of each entry within its expert group
    first_of_group = jnp.searchsorted(sorted_e, sorted_e, side="left")
    rank_sorted = jnp.arange(t * k) - first_of_group
    rank = jnp.zeros((t * k,), jnp.int32).at[order].set(rank_sorted.astype(jnp.int32))
    slot = jnp.where(rank < cap, flat_e * cap + rank, e * cap)  # sentinel drop row

    x_rep = jnp.repeat(xt, k, axis=0)  # (T*k, d)
    buf = jnp.zeros((e * cap + 1, d), x.dtype).at[slot].add(x_rep)
    buf = buf[: e * cap].reshape(e, cap, d)
    buf = shd(buf, ("experts", None, "act_embed"))

    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, p["w_gate"]))
    h = h * jnp.einsum("ecd,edf->ecf", buf, p["w_up"])
    h = shd(h, ("experts", None, "act_mlp"))
    out_buf = jnp.einsum("ecf,efd->ecd", h, p["w_down"])

    flat_out = out_buf.reshape(e * cap, d)
    flat_out = jnp.concatenate([flat_out, jnp.zeros((1, d), x.dtype)], axis=0)
    y_rep = flat_out[slot]  # dropped tokens pick the zero row
    y = (y_rep.reshape(t, k, d) * gates[..., None].astype(x.dtype)).sum(axis=1)

    if "shared" in p:
        y = y + mlp_apply(p["shared"], x, cfg, shd).reshape(t, d)
    return y.reshape(b_, s, d), aux


# ---------------------------------------------------------------------------
# optimized path: explicit expert-parallel all-to-all under shard_map
# (§Perf beyond-paper optimization — see EXPERIMENTS.md. The pjit dispatch
# above lets XLA resolve the token->expert reshard, which materialises the
# full (E, C, d) buffer per device and all-reduces it (~GBs per MoE layer at
# 1M tokens). Here every device routes its own token slice, exchanges ONLY
# real token payloads over the "model" axis (all_to_all there and back), and
# FSDP-gathers its local experts' weights explicitly.)


def _local_dispatch(xt, gates, idx, e, cap, d):
    """Scatter tokens into per-expert slots. xt: (T,d); idx/gates: (T,k)."""
    t, k = idx.shape
    flat_e = idx.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    first = jnp.searchsorted(sorted_e, sorted_e, side="left")
    rank_sorted = jnp.arange(t * k) - first
    rank = jnp.zeros((t * k,), jnp.int32).at[order].set(rank_sorted.astype(jnp.int32))
    slot = jnp.where(rank < cap, flat_e * cap + rank, e * cap)
    x_rep = jnp.repeat(xt, k, axis=0)
    buf = jnp.zeros((e * cap + 1, d), xt.dtype).at[slot].add(x_rep)
    return buf[: e * cap], slot


def moe_apply_alltoall(p: dict, x: Array, cfg, shd: Sharder) -> Tuple[Array, Array]:
    """x: (B,S,d) -> (y, aux). Requires shd.mesh with a "model" axis."""
    from jax.sharding import PartitionSpec as P

    mesh = shd.mesh
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    fsdp = "data" if "data" in mesh.shape else None
    tp = mesh.shape["model"]
    e, k = cfg.num_experts, cfg.experts_per_token
    e_loc = e // tp
    assert e % tp == 0, (e, tp)
    b_, s, d = x.shape

    # weights arrive FSDP-sharded on the d/f dims (P from the rule table);
    # gather them explicitly inside (transpose = reduce-scatter for grads).
    wg_spec = P("model", fsdp, None)
    wd_spec = P("model", None, fsdp)

    def body(x_blk, router, wg, wu, wd):
        # x_blk: (B_loc, S, d) — replicated over "model"; take this shard's
        # token slice so the 16 model shards don't duplicate routing work.
        if fsdp:
            wg_ = jax.lax.all_gather(wg, fsdp, axis=1, tiled=True)
            wu_ = jax.lax.all_gather(wu, fsdp, axis=1, tiled=True)
            wd_ = jax.lax.all_gather(wd, fsdp, axis=2, tiled=True)
        else:
            wg_, wu_, wd_ = wg, wu, wd
        t_loc = x_blk.shape[0] * x_blk.shape[1]
        tpd = t_loc // tp
        my = jax.lax.axis_index("model")
        xt = x_blk.reshape(t_loc, d)
        xs = jax.lax.dynamic_slice_in_dim(xt, my * tpd, tpd, axis=0)

        logits = jnp.einsum("td,de->te", xs.astype(jnp.float32),
                            router.astype(jnp.float32))
        if k == 1 and cfg.name.startswith("llama4"):
            top_val, top_idx = jax.lax.top_k(logits, 1)
            gates = jax.nn.sigmoid(top_val)
            probs = jax.nn.softmax(logits, axis=-1)
        else:
            probs = jax.nn.softmax(logits, axis=-1)
            gates, top_idx = jax.lax.top_k(probs, k)
            gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)

        cap = max(-(-int(cfg.moe_capacity_factor * tpd * k) // e), 4)
        buf, slot = _local_dispatch(xs, gates, top_idx, e, cap, d)
        # (E*cap, d) -> (tp, E_loc*cap, d): destination-major
        send = buf.reshape(tp, e_loc * cap, d)
        recv = jax.lax.all_to_all(send, "model", split_axis=0, concat_axis=0,
                                  tiled=False)
        # recv: (tp, E_loc*cap, d) — rows from every source, my experts only
        hbuf = recv.reshape(tp, e_loc, cap, d).transpose(1, 0, 2, 3) \
                   .reshape(e_loc, tp * cap, d)
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", hbuf, wg_))
        h = h * jnp.einsum("ecd,edf->ecf", hbuf, wu_)
        obuf = jnp.einsum("ecf,efd->ecd", h, wd_)
        back = obuf.reshape(e_loc, tp, cap, d).transpose(1, 0, 2, 3) \
                   .reshape(tp, e_loc * cap, d)
        ret = jax.lax.all_to_all(back, "model", split_axis=0, concat_axis=0,
                                 tiled=False)
        flat = jnp.concatenate(
            [ret.reshape(e * cap, d), jnp.zeros((1, d), x_blk.dtype)], axis=0)
        y_rep = flat[slot]
        ys = (y_rep.reshape(tpd, k, d) * gates[..., None].astype(x_blk.dtype)
              ).sum(axis=1)
        # reassemble the full local token set across the model axis
        y = jax.lax.all_gather(ys, "model", axis=0, tiled=True)
        # aux loss (switch-style), averaged over every shard's token slice
        me = jnp.mean(probs, axis=0)
        ce = jnp.mean(jax.nn.one_hot(top_idx[:, 0], e, dtype=jnp.float32), axis=0)
        aux = e * jnp.sum(me * ce)
        aux = jax.lax.pmean(aux, "model")
        for ax in batch_axes:
            aux = jax.lax.pmean(aux, ax)
        return y.reshape(x_blk.shape), aux

    y, aux = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(batch_axes or None, None, None), P(None, None),
                  wg_spec, wg_spec, wd_spec),
        out_specs=(P(batch_axes or None, None, None), P()),
        check_vma=False,
    )(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])

    if "shared" in p:
        y = y + mlp_apply(p["shared"], x, cfg, shd)
    return y, aux
