"""Pallas TPU flash attention (GQA, causal / sliding-window / softcap).

The NERO discipline applied to attention: the (T, S) score matrix — the
HBM-traffic hot spot the roofline pass identifies in every transformer cell
— never leaves VMEM.  Per (batch, head, q-block) the KV stream is tiled
into VMEM blocks and consumed with an online-softmax dataflow; running max
/ normalizer / accumulator live in VMEM scratch across the kv grid axis
(the Pallas analogue of the paper's per-PE URAM/BRAM intermediate buffers,
with the same load/compute/store overlap via the Pallas grid pipeline).

Grid: (B, H, nq, nk), kv innermost ("arbitrary" — carries scratch state);
GQA maps query head h to kv head h // (H // KH) in the k/v index_maps, so
no KV replication is ever materialized.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  bq: int, bk: int, nk: int, causal: bool, window: int,
                  softcap: float, scale: float):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32) * scale          # (bq, hd)
    k = k_ref[0, 0].astype(jnp.float32)                  # (bk, hd)
    v = v_ref[0, 0].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (bq, bk)
    if softcap:
        s = jnp.tanh(s / softcap) * softcap

    qpos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kpos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = jnp.ones((bq, bk), jnp.bool_)
    if causal:
        mask &= kpos <= qpos
    if window:
        mask &= (qpos - kpos) < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]                                  # (bq, 1)
    l_prev = l_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)                               # (bq, bk)
    corr = jnp.exp(m_prev - m_new)                       # (bq, 1)
    l_ref[...] = l_prev * corr + p.sum(axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _finalize():
        o_ref[0, 0] = (acc_ref[...]
                       / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def flash_mha_pallas(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                     causal: bool = True, window: int = 0,
                     softcap: float = 0.0, block_q: int = 128,
                     block_k: int = 128,
                     interpret: bool = False) -> jnp.ndarray:
    """q: (B, T, H, hd); k, v: (B, S, KH, hd).  T % block_q == S % block_k
    == 0 (pick blocks with kernels.flash_attention.ops.auto_blocks)."""
    b, t, h, hd = q.shape
    s, kh = k.shape[1], k.shape[2]
    g = h // kh
    bq = min(block_q, t)
    bk = min(block_k, s)
    if t % bq or s % bk:
        raise ValueError(f"(T={t}, S={s}) must tile by ({bq}, {bk})")
    nq, nk = t // bq, s // bk

    qt = q.transpose(0, 2, 1, 3)                         # (B, H, T, hd)
    kt = k.transpose(0, 2, 1, 3)                         # (B, KH, S, hd)
    vt = v.transpose(0, 2, 1, 3)

    kernel = functools.partial(
        _flash_kernel, bq=bq, bk=bk, nk=nk, causal=causal, window=window,
        softcap=softcap, scale=hd ** -0.5)
    out = pl.pallas_call(
        kernel,
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, bk, hd),
                         lambda bi, hi, qi, ki: (bi, hi // g, ki, 0)),
            pl.BlockSpec((1, 1, bk, hd),
                         lambda bi, hi, qi, ki: (bi, hi // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd),
                               lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, t, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),            # running max
            pltpu.VMEM((bq, 1), jnp.float32),            # running sum
            pltpu.VMEM((bq, hd), jnp.float32),           # output accum
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name="nero_flash_mha",
    )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3)                     # (B, T, H, hd)
