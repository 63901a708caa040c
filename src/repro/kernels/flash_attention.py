"""Pallas kernel: causal GQA flash attention (serving/prefill hot path).

Not part of the paper's contribution (the models default to the pure-JAX
online-softmax attention in models/layers.py, which is what the dry-run
lowers); this kernel is the TPU-performance path for 32k-prefill serving:
HBM traffic O(S*D) instead of O(S^2) logits.

Grid: (B*H, S/bq, S/bk), kv innermost; running (m, l, acc) in VMEM scratch.
GQA: query head h reads kv head h // group_size via the BlockSpec index map.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import I0, interpret_default


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
            *, scale, causal, k_steps, bq, bk):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -1e30)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32) * scale          # (bq, d)
    k = k_ref[0].astype(jnp.float32)                  # (bk, d)
    v = v_ref[0].astype(jnp.float32)
    logits = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )                                                  # (bq, bk)
    if causal:
        qi = pl.program_id(1)
        q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        logits = jnp.where(q_pos >= k_pos, logits, -1e30)
    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1, keepdims=True))
    p = jnp.exp(logits - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    m_scr[...] = m_new

    @pl.when(ki == k_steps - 1)
    def _done():
        o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)).astype(
            o_ref.dtype
        )


@functools.partial(
    jax.jit, static_argnames=("causal", "bq", "bk", "interpret")
)
def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    bq: int = 256,
    bk: int = 256,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """q: (B, S, H, D); k, v: (B, S, KV, D); H = KV * G.  Returns (B,S,H,D)."""
    if interpret is None:
        interpret = interpret_default()
    b, s, h, d = q.shape
    _, sk, kv, _ = k.shape
    g = h // kv
    bq = min(bq, s)
    bk = min(bk, sk)
    if s % bq or sk % bk:
        raise ValueError(f"seq ({s},{sk}) not divisible by blocks ({bq},{bk})")
    scale = 1.0 / math.sqrt(d)
    qr = q.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    kr = k.transpose(0, 2, 1, 3).reshape(b * kv, sk, d)
    vr = v.transpose(0, 2, 1, 3).reshape(b * kv, sk, d)
    k_steps = sk // bk

    out = pl.pallas_call(
        functools.partial(
            _kernel, scale=scale, causal=causal, k_steps=k_steps, bq=bq, bk=bk
        ),
        grid=(b * h, s // bq, k_steps),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, qi, ki: (bh, qi, I0)),
            pl.BlockSpec((1, bk, d), lambda bh, qi, ki: (bh // g, ki, I0)),
            pl.BlockSpec((1, bk, d), lambda bh, qi, ki: (bh // g, ki, I0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda bh, qi, ki: (bh, qi, I0)),
        out_shape=jax.ShapeDtypeStruct((b * h, s, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention",
    )(qr, kr, vr)
    return out.reshape(b, h, s, d).transpose(0, 2, 1, 3)
