"""Public flash-attention wrapper: pads sequence dims to tile multiples,
compiles or interprets per :func:`repro.kernels.interpret_mode`, and
exposes a differentiable op —
the forward is the Pallas kernel; the backward is the XLA-native
recompute gradient of the oracle (the paper's serving regime never
backprops through attention; training falls back to a fused-by-XLA path,
recorded in DESIGN.md §2)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import interpret_mode
from repro.kernels.flash_attention import kernel, ref


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash_attention(q, k, v, causal: bool = True, window: int = 0,
                    scale: float | None = None):
    return _forward(q, k, v, causal, window, scale)


def _forward(q, k, v, causal, window, scale):
    b, hq, sq, d = q.shape
    sk = k.shape[2]
    blk_q = min(128, max(8, sq))
    blk_k = min(128, max(8, sk))
    pad_q = (-sq) % blk_q
    pad_k = (-sk) % blk_k
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    # true lengths are carried via sq/sk inside the kernel mask
    out, _ = kernel.flash_attention(
        qp, kp, vp, causal=causal, window=window, scale=scale,
        blk_q=blk_q, blk_k=blk_k, interpret=interpret_mode())
    # kernel masks by absolute position, but padded q rows still emit
    out = out[:, :, :sq]
    return out


def _fwd(q, k, v, causal, window, scale):
    return _forward(q, k, v, causal, window, scale), (q, k, v)


def _bwd(causal, window, scale, res, g):
    q, k, v = res
    _, vjp = jax.vjp(
        lambda q_, k_, v_: ref.attention(q_, k_, v_, causal=causal,
                                         window=window, scale=scale),
        q, k, v)
    return vjp(g)


flash_attention.defvjp(_fwd, _bwd)
