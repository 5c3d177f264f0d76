"""Mixture-of-Experts FFN with top-k routing and capacity-bounded,
sort-free dispatch (GShard/Switch lineage, MegaBlocks-style gathers).

TPU adaptation (DESIGN.md §5): the classic GShard one-hot dispatch einsum
(N·E·C·d FLOPs) is replaced by scatter/gather through per-expert
capacity buffers — FLOPs stay proportional to *active* parameters:

    router logits (N, E) → top-k ids/weights (N, k)
    position-in-expert  = masked running count (cumsum over assignments)
    expert buffer (E, C, d)  ← scatter of kept assignments
    expert FFN (E, C, d) × (E, d, f) batched matmuls (SwiGLU)
    token out ← gather back × routing weight, summed over the k slots

Experts are **TP-sharded** on the mesh model axis (each expert's ffn dim
split) — valid for any expert count (Mixtral's 8 < 16-wide model axis
included). Aux load-balancing loss follows Switch (§ loss = E·Σ f_e·P_e).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.distributed.sharding import shard
from repro.models import layers

Array = jax.Array


def init(key: Array, d_model: int, d_ff: int, n_experts: int,
         dtype=jnp.float32) -> dict:
    ks = jax.random.split(key, 4)
    s_in = d_model ** -0.5
    s_out = d_ff ** -0.5

    def ew(k, shape, scale):
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(dtype)

    return {
        "router": layers.dense_init(ks[0], d_model, n_experts, jnp.float32),
        "w_gate": ew(ks[1], (n_experts, d_model, d_ff), s_in),
        "w_up": ew(ks[2], (n_experts, d_model, d_ff), s_in),
        "w_down": ew(ks[3], (n_experts, d_ff, d_model), s_out),
    }


class MoEStats(NamedTuple):
    aux_loss: Array       # Switch load-balance loss
    dropped_frac: Array   # fraction of assignments dropped at capacity


def forward(params: dict, x: Array, *, n_experts: int, top_k: int,
            capacity_factor: float = 1.25) -> tuple[Array, MoEStats]:
    """x: (B, S, D) -> (B, S, D)."""
    b, s, d = x.shape
    n = b * s
    xt = x.reshape(n, d)

    logits = layers.dense(params["router"], xt).astype(jnp.float32)  # (N, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_e = jax.lax.top_k(probs, top_k)                       # (N, k)
    top_w = top_w / jnp.maximum(top_w.sum(axis=-1, keepdims=True), 1e-9)

    capacity = max(int(n * top_k * capacity_factor / n_experts), 1)

    # position of each assignment within its expert (running count over
    # the flattened (token, slot) order)
    flat_e = top_e.reshape(-1)                                       # (N·k,)
    onehot = jax.nn.one_hot(flat_e, n_experts, dtype=jnp.int32)      # (N·k, E)
    pos_in_e = (jnp.cumsum(onehot, axis=0) - 1)                      # inclusive
    pos = jnp.take_along_axis(pos_in_e, flat_e[:, None], axis=1)[:, 0]
    flat_keep = pos < capacity
    keep = flat_keep.reshape(n, top_k)

    # dispatch: ONE 2-D scatter-add into the (E, C, d) buffer.
    # (Two alternatives were tried and refuted, see EXPERIMENTS.md §Perf:
    # a per-slot scatter chain keeps top_k cotangent copies of the buffer
    # live in backward (8×5.4 GB for OLMoE); a flat (E·C, d) segment_sum
    # loses the sharding relation and GSPMD replicates everything.)
    flat_tok = jnp.repeat(jnp.arange(n), top_k)                      # (N·k,)
    safe_pos = jnp.where(flat_keep, pos, 0)
    updates = jnp.take(xt, flat_tok, axis=0) * flat_keep[:, None
                                                         ].astype(xt.dtype)
    updates = shard(updates, "moe_flat", None)
    buf = jnp.zeros((n_experts, capacity, d), xt.dtype)
    buf = buf.at[flat_e, safe_pos].add(updates, mode="drop")
    # capacity axis sharded over data (E·C·d would replicate to tens of
    # GB otherwise)
    buf = shard(buf, "experts", "moe_capacity", None)

    # expert SwiGLU, TP-sharded on the ffn dim; weights cast to the
    # activation dtype (mixed-dtype einsums would upcast the E·C·d
    # dispatch buffers to f32 — gigabytes per device)
    w_gate = shard(params["w_gate"], "experts", None, "expert_ff"
                   ).astype(buf.dtype)
    w_up = shard(params["w_up"], "experts", None, "expert_ff"
                 ).astype(buf.dtype)
    w_down = shard(params["w_down"], "experts", "expert_ff", None
                   ).astype(buf.dtype)
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, w_gate,
                               preferred_element_type=jnp.float32)) * \
        jnp.einsum("ecd,edf->ecf", buf, w_up,
                   preferred_element_type=jnp.float32)
    h = h.astype(xt.dtype)
    out_buf = jnp.einsum("ecf,efd->ecd", h, w_down,
                         preferred_element_type=jnp.float32).astype(xt.dtype)

    # combine: 2-D gather of each assignment's expert output + ONE
    # segment_sum back to tokens (single-op both ways — no chains)
    flat_out = out_buf[flat_e, safe_pos]                             # (N·k, d)
    flat_out = shard(flat_out, "moe_flat", None)
    w = (flat_keep * top_w.reshape(-1)).astype(xt.dtype)
    out = jax.ops.segment_sum(flat_out * w[:, None], flat_tok,
                              num_segments=n).astype(xt.dtype)

    # Switch aux loss: E · Σ_e f_e · P_e
    f_e = jnp.mean(
        (jax.nn.one_hot(top_e, n_experts).sum(axis=1) > 0), axis=0)
    p_e = probs.mean(axis=0)
    aux = n_experts * jnp.sum(f_e * p_e)
    stats = MoEStats(aux_loss=aux,
                     dropped_frac=1.0 - keep.mean())
    return out.reshape(b, s, d), stats


# ---------------------------------------------------------------------------
# shard_map implementation (hillclimb: EXPERIMENTS.md §Perf, mixtral cell)
#
# The GSPMD path above leaves two structural costs on the table:
#   1. the position-in-expert cumsum runs over the GLOBAL (N·k, E) plane —
#      GSPMD cannot partition a prefix-sum, so it replicates it;
#   2. dispatch/combine scatters cross data shards, and FSDP weight
#      gathers are emitted in f32.
# Here each data shard dispatches its OWN tokens into its OWN capacity
# buffer (local cumsum — zero dispatch collectives, the standard
# "local capacity" semantics of data-parallel MoE), experts stay
# TP-sharded on the model axis (one psum after the down-projection), and
# the FSDP weight gather happens explicitly in bf16 (half the bytes of
# the f32 auto-gather).
# ---------------------------------------------------------------------------

def _local_moe_body(xt, router_w, w_gate, w_up, w_down, *,
                    n_experts: int, top_k: int, capacity: int,
                    model_axis):
    """Per-shard MoE: xt (n_local, d) with FULLY LOCAL dispatch."""
    n, d = xt.shape
    logits = jnp.matmul(xt, router_w.astype(xt.dtype),
                        preferred_element_type=jnp.float32)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    top_w, top_e = jax.lax.top_k(probs, top_k)
    top_w = top_w / jnp.maximum(top_w.sum(axis=-1, keepdims=True), 1e-9)

    flat_e = top_e.reshape(-1)
    onehot = jax.nn.one_hot(flat_e, n_experts, dtype=jnp.int32)
    pos = jnp.take_along_axis(jnp.cumsum(onehot, axis=0) - 1,
                              flat_e[:, None], axis=1)[:, 0]
    flat_keep = pos < capacity
    safe_pos = jnp.where(flat_keep, pos, 0)
    flat_tok = jnp.repeat(jnp.arange(n), top_k)

    updates = jnp.take(xt, flat_tok, axis=0) * flat_keep[:, None
                                                         ].astype(xt.dtype)
    buf = jnp.zeros((n_experts, capacity, d), xt.dtype)
    buf = buf.at[flat_e, safe_pos].add(updates, mode="drop")

    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, w_gate,
                               preferred_element_type=jnp.float32)) * \
        jnp.einsum("ecd,edf->ecf", buf, w_up,
                   preferred_element_type=jnp.float32)
    h = h.astype(xt.dtype)
    out_buf = jnp.einsum("ecf,efd->ecd", h, w_down,
                         preferred_element_type=jnp.float32)
    out_buf = out_buf.astype(xt.dtype)

    flat_out = out_buf[flat_e, safe_pos]
    w = (flat_keep * top_w.reshape(-1)).astype(xt.dtype)
    out = jax.ops.segment_sum(flat_out * w[:, None], flat_tok,
                              num_segments=n).astype(xt.dtype)
    # TP partial sums: combine is linear in out_buf, so the psum commutes
    # past it — reducing the (N, d) token plane (1.5 GB) instead of the
    # (E, C=N·k·cf/E, d) buffer (3.75 GB) cuts the dominant collective
    # 2.5× (capacity expansion never crosses the wire)
    if model_axis is not None:
        out = jax.lax.psum(out, model_axis)

    f_e = jnp.mean((jax.nn.one_hot(top_e, n_experts).sum(axis=1) > 0),
                   axis=0)
    aux = n_experts * jnp.sum(f_e * probs.mean(axis=0))
    dropped = 1.0 - flat_keep.mean()
    return out, aux, dropped


def forward_shard_map(params: dict, x: Array, *, n_experts: int, top_k: int,
                      capacity_factor: float = 1.25
                      ) -> tuple[Array, MoEStats]:
    """shard_map MoE (see header). Falls back to :func:`forward` when no
    mesh is active (CPU unit tests)."""
    from jax.sharding import PartitionSpec as P
    from repro.distributed import sharding as shd

    mesh = shd._mesh()
    if mesh is None:
        return forward(params, x, n_experts=n_experts, top_k=top_k,
                       capacity_factor=capacity_factor)
    data_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    model_axis = "model" if "model" in mesh.axis_names else None
    n_data = 1
    for a in data_axes:
        n_data *= mesh.shape[a]
    n_model = mesh.shape.get("model", 1)

    b, s, d = x.shape
    n_local = (b * s) // n_data
    capacity = max(int(n_local * top_k * capacity_factor / n_experts), 1)

    def body(xl, rw, wg, wu, wd):
        bl, sl, _ = xl.shape
        # explicit FSDP gather of this layer's expert weights, in bf16
        # (the f32 auto-gather at the boundary would double the traffic)
        def regather(wp):                        # (E, D/|data|, F/|model|)
            wp = wp.astype(xl.dtype)
            return jax.lax.all_gather(wp, data_axes, axis=1, tiled=True)

        out, aux, dropped = _local_moe_body(
            xl.reshape(bl * sl, d), rw, regather(wg), regather(wu),
            jnp.swapaxes(jax.lax.all_gather(
                jnp.swapaxes(wd.astype(xl.dtype), 1, 2),
                data_axes, axis=1, tiled=True), 1, 2),
            n_experts=n_experts, top_k=top_k, capacity=capacity,
            model_axis=model_axis)
        aux = jax.lax.pmean(aux, data_axes)
        dropped = jax.lax.pmean(dropped, data_axes)
        if model_axis is not None:
            # shards along model computed identical stats; keep one copy
            aux = jax.lax.pmean(aux, model_axis)
            dropped = jax.lax.pmean(dropped, model_axis)
        return out.reshape(bl, sl, d), aux, dropped

    batch_spec = P(data_axes if len(data_axes) > 1 else data_axes[0],
                   None, None)
    out, aux, dropped = jax.shard_map(
        body, mesh=mesh,
        in_specs=(batch_spec,
                  P(None, None),                       # router (replicated)
                  P(None, data_axes, "model"),         # w_gate (E, D, F)
                  P(None, data_axes, "model"),         # w_up
                  P(None, "model", data_axes)),        # w_down (E, F, D)
        out_specs=(batch_spec, P(), P()),
        check_vma=False,
    )(x, params["router"]["w"], params["w_gate"], params["w_up"],
      params["w_down"])
    return out, MoEStats(aux_loss=aux, dropped_frac=dropped)
