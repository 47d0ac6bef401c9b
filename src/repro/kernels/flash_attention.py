"""SPM-tiled flash attention (online softmax in VMEM).

This is the LM-scale payoff of the paper's SPM discipline: the S x S score
matrix NEVER touches HBM — Q/K/V tiles stream through VMEM (kmemld), the
online-softmax state (m, l, acc) lives in VMEM scratch across the KV grid
dimension (SPM-resident accumulators), and only the [Sq, hd] output is
written back (kmemstr). GQA (q-head groups share a KV head), causal and
sliding-window masking supported; fully-masked KV blocks are skipped.

Oracle: repro.models.layers.attention_ref / flash_attention_xla (identical
math — the XLA path used by the dry-run).
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import interpret_mode, pick_block

NEG_INF = -1e30
_LANES = 128


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  bq: int, bk: int, n_k: int, causal: bool, window: int,
                  scale: float, q_offset: int):
    _, iq, ik = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_pos = q_offset + iq * bq + jax.lax.broadcasted_iota(
        jnp.int32, (bq, bk), 0)
    k_pos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    visible = True
    if causal:
        visible = q_pos >= k_pos
    if window:
        visible = visible & (q_pos - k_pos < window)

    q = q_ref[0].astype(jnp.float32)                   # [bq, hd]
    k = k_ref[0].astype(jnp.float32)                   # [bk, hd]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if causal or window:
        s = jnp.where(visible, s, NEG_INF)

    m_prev = m_ref[:, :1]                              # [bq, 1]
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)
    if causal or window:
        p = jnp.where(visible, p, 0.0)
    corr = jnp.exp(m_prev - m_new)                     # [bq, 1]
    l_new = l_ref[:, :1] * corr + jnp.sum(p, axis=1, keepdims=True)
    v = v_ref[0].astype(jnp.float32)                   # [bk, hd]
    pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    acc_ref[...] = acc_ref[...] * corr + pv
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ik == n_k - 1)
    def _flush():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[:, :1], 1e-30)).astype(o_ref.dtype)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int = 0, bq: int = 512,
                    bk: int = 512, q_offset: int = 0) -> jax.Array:
    """q: [B, H, Sq, hd]; k, v: [B, KV, Skv, hd]; H = KV * G. -> [B,H,Sq,hd]
    """
    B, H, Sq, hd = q.shape
    _, KV, Skv, _ = k.shape
    G = H // KV
    bq = pick_block(Sq, bq, align=8)
    bk = pick_block(Skv, bk, align=8)
    n_q, n_k = Sq // bq, Skv // bk
    scale = 1.0 / np.sqrt(hd)

    qr = q.reshape(B * H, Sq, hd)
    kr = k.reshape(B * KV, Skv, hd)

    out = pl.pallas_call(
        functools.partial(_flash_kernel, bq=bq, bk=bk, n_k=n_k, causal=causal,
                          window=window, scale=scale, q_offset=q_offset),
        grid=(B * H, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, bk, hd),
                         lambda bh, i, j, G=G: (bh // G, j, 0)),
            pl.BlockSpec((1, bk, hd),
                         lambda bh, i, j, G=G: (bh // G, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), lambda bh, i, j: (bh, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, _LANES), jnp.float32),     # m
            pltpu.VMEM((bq, _LANES), jnp.float32),     # l
            pltpu.VMEM((bq, hd), jnp.float32),         # acc
        ],
        interpret=interpret_mode(),
    )(qr, kr.reshape(B * KV, Skv, hd), v.reshape(B * KV, Skv, hd))
    return out.reshape(B, H, Sq, hd)
