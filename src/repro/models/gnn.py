"""GatedGCN (Bresson & Laurent 2017; benchmarked in arXiv:2003.00982).

JAX has no CSR SpMM — message passing is built on the edge-index →
``jax.ops.segment_sum`` scatter pattern (the brief's required substrate):

    e'_ij  = e_ij + ReLU(Norm(E1·h_i + E2·h_j + E3·e_ij))
    σ_ij   = sigmoid(e'_ij)
    agg_i  = Σ_j σ_ij ⊙ (B2·h_j)  /  (Σ_j σ_ij + ε)       (gated mean)
    h'_i   = h_i + ReLU(Norm(B1·h_i + agg_i))

Adaptation note (DESIGN.md): BatchNorm → LayerNorm (BN statistics don't
compose across edge-sharded devices; LN is the standard substitution in
distributed GNN training).

Scale-out: edge planes (src, dst, e) are sharded over the mesh
("edges" logical axis); node features stay replicated; each shard's
partial ``segment_sum`` is completed by XLA's scatter-add all-reduce.
Graphs are padded to fixed shapes (PAD edges point at a sink node).
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from repro.distributed.sharding import shard
from repro.models import layers

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class GatedGCNConfig:
    n_layers: int = 16
    d_hidden: int = 70
    d_feat: int = 1433
    d_edge_feat: int = 0       # 0 → learned constant edge init
    n_classes: int = 16
    graph_level: bool = False  # molecule cells: per-graph readout
    remat: bool = True
    impl: str = "gspmd"        # "gspmd" | "partitioned" (§Perf)
    bf16_gather: bool = False  # partitioned: gather node states in bf16


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=["node_feat", "edge_src", "edge_dst", "edge_mask",
                 "node_mask", "labels", "graph_id"],
    meta_fields=["n_graphs"])
@dataclasses.dataclass(frozen=True)
class GraphBatch:
    """Fixed-shape padded (batch of) graph(s).

    Batched small graphs are flattened into one disjoint union; ``graph_id``
    maps nodes to their graph (for graph-level readout). PAD edges use
    src=dst=n_nodes-1 with edge_mask=0; PAD nodes have node_mask=0.
    ``n_graphs`` is static metadata (it feeds segment counts).
    """
    node_feat: Array            # (N, d_feat) f32
    edge_src: Array             # (E,) i32
    edge_dst: Array             # (E,) i32
    edge_mask: Array            # (E,) f32
    node_mask: Array            # (N,) f32
    labels: Array               # (N,) or (G,) i32
    graph_id: Array             # (N,) i32 (zeros for single-graph)
    n_graphs: int = 1


def init(key: Array, cfg: GatedGCNConfig) -> dict:
    ks = jax.random.split(key, 4 + cfg.n_layers)
    d = cfg.d_hidden

    def layer_init(k):
        kk = jax.random.split(k, 6)
        return {
            "E1": layers.dense_init(kk[0], d, d),
            "E2": layers.dense_init(kk[1], d, d),
            "E3": layers.dense_init(kk[2], d, d),
            "B1": layers.dense_init(kk[3], d, d),
            "B2": layers.dense_init(kk[4], d, d),
            "norm_h": layers.layernorm_init(d),
            "norm_e": layers.layernorm_init(d),
        }

    stacked = jax.vmap(layer_init)(jax.random.split(ks[0], cfg.n_layers))
    return {
        "embed_h": layers.dense_init(ks[1], cfg.d_feat, d),
        "embed_e": (layers.dense_init(ks[2], cfg.d_edge_feat, d)
                    if cfg.d_edge_feat > 0
                    else {"const": jnp.zeros((d,), jnp.float32)}),
        "layers": stacked,
        "head": layers.dense_init(ks[3], d, cfg.n_classes),
    }


def _layer(lp: dict, h: Array, e: Array, src: Array, dst: Array,
           edge_mask: Array, n_nodes: int) -> tuple[Array, Array]:
    h_src = jnp.take(h, src, axis=0)
    h_dst = jnp.take(h, dst, axis=0)
    h_src = shard(h_src, "edges", None)
    h_dst = shard(h_dst, "edges", None)

    e_new = (layers.dense(lp["E1"], h_dst) + layers.dense(lp["E2"], h_src)
             + layers.dense(lp["E3"], e))
    e = e + jax.nn.relu(layers.layernorm(lp["norm_e"], e_new))
    gate = jax.nn.sigmoid(e) * edge_mask[:, None]

    msg = gate * layers.dense(lp["B2"], h_src)
    msg = shard(msg, "edges", None)
    num = jax.ops.segment_sum(msg, dst, num_segments=n_nodes)
    den = jax.ops.segment_sum(gate, dst, num_segments=n_nodes)
    num = shard(num, "nodes", None)
    den = shard(den, "nodes", None)
    agg = num / (den + 1e-6)

    h_new = layers.dense(lp["B1"], h) + agg
    h = h + jax.nn.relu(layers.layernorm(lp["norm_h"], h_new))
    # node planes sharded between layers: at ogb_products scale a
    # replicated (N, d) carry × n_layers of saved activations would be
    # tens of GB per device
    return shard(h, "nodes", None), e


def forward(params: dict, cfg: GatedGCNConfig, batch: GraphBatch) -> Array:
    """Returns logits: (N, n_classes) node-level or (G, n_classes) graph-level."""
    n_nodes = batch.node_feat.shape[0]
    h = shard(layers.dense(params["embed_h"], batch.node_feat),
              "nodes", None)
    if cfg.d_edge_feat > 0:
        raise NotImplementedError("edge-featured inputs not used by the assigned cells")
    e = jnp.broadcast_to(params["embed_e"]["const"],
                         (batch.edge_src.shape[0], cfg.d_hidden))
    e = shard(e, "edges", None)

    def scan_body(carry, lp):
        h_c, e_c = carry
        def fn(hh, ee, p):
            return _layer(p, hh, ee, batch.edge_src, batch.edge_dst,
                          batch.edge_mask, n_nodes)
        if cfg.remat:
            fn = jax.checkpoint(fn)
        h_n, e_n = fn(h_c, e_c, lp)
        return (h_n, e_n), None

    (h, e), _ = jax.lax.scan(scan_body, (h, e), params["layers"])

    if cfg.graph_level:
        pooled = jax.ops.segment_sum(h * batch.node_mask[:, None],
                                     batch.graph_id,
                                     num_segments=batch.n_graphs)
        counts = jax.ops.segment_sum(batch.node_mask, batch.graph_id,
                                     num_segments=batch.n_graphs)
        pooled = pooled / jnp.maximum(counts, 1.0)[:, None]
        return layers.dense(params["head"], pooled)
    return layers.dense(params["head"], h)


def loss_fn(params: dict, cfg: GatedGCNConfig, batch: GraphBatch
            ) -> tuple[Array, dict]:
    logits = forward(params, cfg, batch)
    if cfg.graph_level:
        loss = layers.softmax_xent(logits, batch.labels)
    else:
        mask = batch.node_mask
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        ll = jnp.take_along_axis(logp, jnp.clip(batch.labels, 0, None)[:, None],
                                 axis=-1)[:, 0]
        loss = -(ll * mask).sum() / jnp.maximum(mask.sum(), 1.0)
    return loss, {"loss": loss}


# ---------------------------------------------------------------------------
# partitioned implementation (hillclimb: EXPERIMENTS.md §Perf, ogb cell)
#
# The GSPMD baseline psums full (N, d) node planes per layer (num + den,
# f32, fwd + bwd) because edge-sharded segment_sum cannot prove locality.
# Owner-computes partitioning makes aggregation LOCAL: each shard owns a
# contiguous node range and every edge whose dst lies in its range (the
# data pipeline's range partitioner, graph.partition_by_dst). Per layer
# the only collective is ONE all-gather of the node states (src gathers
# may touch any node); its transpose is one reduce-scatter.
# ---------------------------------------------------------------------------

def forward_partitioned(params: dict, cfg: GatedGCNConfig,
                        batch: GraphBatch) -> Array:
    """shard_map GatedGCN. Contract: edges are dst-range partitioned
    (edge i on shard s ⇒ dst[i] ∈ [s·n_local, (s+1)·n_local)); node
    planes are sharded by the same ranges. Falls back to :func:`forward`
    off-mesh."""
    from jax.sharding import PartitionSpec as P
    from repro.distributed import sharding as shd

    mesh = shd._mesh()
    if mesh is None:
        return forward(params, cfg, batch)
    axes = tuple(a for a in ("data", "model") if a in mesh.axis_names)
    n_shards = 1
    for a in axes:
        n_shards *= mesh.shape[a]
    n_nodes = batch.node_feat.shape[0]
    n_local = n_nodes // n_shards

    def body(p, feat_l, src, dst, emask):
        offset = jax.lax.axis_index(axes) * n_local
        h = layers.dense(p["embed_h"], feat_l)               # (n_local, d)
        e = jnp.broadcast_to(p["embed_e"]["const"],
                             (src.shape[0], cfg.d_hidden))

        def scan_body(carry, lp):
            h_c, e_c = carry

            def one_layer(h_i, e_i, lpp):
                hg = (h_i.astype(jnp.bfloat16) if cfg.bf16_gather else h_i)
                h_full = jax.lax.all_gather(hg, axes, axis=0, tiled=True)
                h_full = h_full.astype(h_i.dtype)
                h_src = jnp.take(h_full, src, axis=0)
                h_dst = jnp.take(h_full, dst, axis=0)
                e_new = (layers.dense(lpp["E1"], h_dst)
                         + layers.dense(lpp["E2"], h_src)
                         + layers.dense(lpp["E3"], e_i))
                e_i = e_i + jax.nn.relu(layers.layernorm(lpp["norm_e"],
                                                         e_new))
                gate = jax.nn.sigmoid(e_i) * emask[:, None]
                msg = gate * layers.dense(lpp["B2"], h_src)
                dst_local = dst - offset                    # owned range
                num = jax.ops.segment_sum(msg, dst_local,
                                          num_segments=n_local)
                den = jax.ops.segment_sum(gate, dst_local,
                                          num_segments=n_local)
                agg = num / (den + 1e-6)
                h_new = layers.dense(lpp["B1"], h_i) + agg
                h_i = h_i + jax.nn.relu(layers.layernorm(lpp["norm_h"],
                                                         h_new))
                return h_i, e_i

            fn = one_layer
            if cfg.remat:
                fn = jax.checkpoint(fn)
            h_n, e_n = fn(h_c, e_c, lp)
            return (h_n, e_n), None

        (h, e), _ = jax.lax.scan(scan_body, (h, e), p["layers"])
        return layers.dense(p["head"], h)                    # (n_local, C)

    ax = axes if len(axes) > 1 else axes[0]
    logits = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(), P(ax, None), P(ax), P(ax), P(ax)),
        out_specs=P(ax, None),
        check_vma=False,
    )(params, batch.node_feat, batch.edge_src, batch.edge_dst,
      batch.edge_mask)
    return logits


def loss_fn_partitioned(params: dict, cfg: GatedGCNConfig,
                        batch: GraphBatch) -> tuple[Array, dict]:
    logits = forward_partitioned(params, cfg, batch)
    mask = batch.node_mask
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    ll = jnp.take_along_axis(logp, jnp.clip(batch.labels, 0, None)[:, None],
                             axis=-1)[:, 0]
    loss = -(ll * mask).sum() / jnp.maximum(mask.sum(), 1.0)
    return loss, {"loss": loss}
