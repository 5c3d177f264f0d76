"""8-bit scalar quantization codec (DESIGN.md §7) — Faiss's
``SQ8``/``QT_8bit``: a per-dimension min/max affine map onto one byte,

    code_d = round((x_d − lo_d) / scale_d),   scale_d = (hi_d − lo_d)/255

so a document costs h bytes — 4× less doc-plane HBM and gather traffic
than the flat codec — while scoring stays a (dequantized) exact dot
product:

    <q, x̂> = Σ_d q_d·(code_d·scale_d + lo_d)
           = <q·scale, code> + <q, lo>

i.e. one pre-scaled einsum over the gathered byte rows plus a per-query
bias, no lookup tables.  Reconstruction error is bounded by scale/2 per
dimension (asserted by ``tests/test_codecs.py``), which at typical
embedding ranges sits between PQ and flat on the quality–size trade —
the paper's "robust across index settings" axis (Table 3).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.codecs import base

Array = jax.Array
# search-side contractions run at full f32 precision on every backend,
# matching the fused kernel (DESIGN.md §11's tolerance is an f32 bound)
HIGHEST = jax.lax.Precision.HIGHEST


class SQ8Codec(base.Codec):
    name = "sq8"

    def train(self, key: Array, embeddings: Array, *, pq_m: int = 8,
              pq_k: int = 256) -> dict:
        x = embeddings.astype(jnp.float32)
        lo, hi = x.min(axis=0), x.max(axis=0)
        span = hi - lo
        # constant dims quantize to code 0 and decode to lo exactly
        scale = jnp.where(span > 0, span / 255.0, 1.0)
        return {"lo": lo, "scale": scale}

    def encode(self, params: dict, embeddings: Array) -> dict:
        x = embeddings.astype(jnp.float32)
        q = jnp.round((x - params["lo"]) / params["scale"])
        return {"codes": jnp.clip(q, 0, 255).astype(jnp.uint8)}

    def decode(self, params: dict, doc_planes: dict) -> Array:
        codes = doc_planes["codes"].astype(jnp.float32)
        return codes * params["scale"] + params["lo"]

    def abstract(self, n_docs: int, hidden: int, *, pq_m: int = 8,
                 pq_k: int = 256):
        sds = jax.ShapeDtypeStruct
        params = {"lo": sds((hidden,), jnp.float32),
                  "scale": sds((hidden,), jnp.float32)}
        return params, {"codes": sds((n_docs, hidden), jnp.uint8)}

    def make_scorer(self, params: dict, doc_planes: dict, queries: Array,
                    use_kernel: bool = False):
        q = queries.astype(jnp.float32)
        q_scaled = q * params["scale"]                   # (B, h)
        bias = jnp.matmul(q, params["lo"], precision=HIGHEST)  # (B,)
        codes_plane = doc_planes["codes"]

        def score(ids: Array, live: Array = None) -> Array:
            if use_kernel:
                # fused gather+dot; the bias is added AFTER the in-kernel
                # mask (-inf + bias = -inf, so masked lanes stay masked)
                from repro.kernels.sq8_dot import ops as sq8_ops
                lv = (jnp.ones(ids.shape, jnp.int32) if live is None
                      else live)
                return sq8_ops.sq8_dot_fused(
                    q_scaled, codes_plane, jnp.clip(ids, 0, None), lv
                ) + bias[:, None]
            rows = base.gather_rows(codes_plane, ids)    # (B, C, h) u8
            s = (jnp.einsum("bh,bch->bc", q_scaled,
                            rows.astype(jnp.float32), precision=HIGHEST)
                 + bias[:, None])
            return s if live is None else jnp.where(live, s, -jnp.inf)

        return score
