"""Pallas TPU fused cross-entropy: streaming logsumexp over vocab tiles.

The LM-head NLL is the last memory hot spot the roofline flags on train
cells: the chunked-JAX path still materializes (rows, V_local) f32 logits
per chunk in HBM (268 MB/chunk for gemma3's 262k vocab at TP=16).  The
NERO discipline applies once more: tile the vocab axis into VMEM-sized
blocks, keep the online max / normalizer / gold-logit accumulators in VMEM
scratch across the vocab grid axis, and never write a logit to HBM.

grid = (N/bn, Vp/bv), vocab innermost ("arbitrary", carries scratch);
per-row NLL comes out (N, 1) f32; the scalar reduction happens outside.
Forward-only by design — the training path keeps the differentiable
chunked-JAX form; this kernel serves eval/scoring and the roofline
variant's accounting twin.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _xent_kernel(h_ref, head_ref, tgt_ref, valid_ref, out_ref,
                 m_ref, l_ref, g_ref, *, bn: int, bv: int, nv: int,
                 vocab: int, softcap: float):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        g_ref[...] = jnp.zeros_like(g_ref)

    h = h_ref[0].astype(jnp.float32)                     # (bn, D)
    w = head_ref[...].astype(jnp.float32)                # (D, bv)
    lg = jax.lax.dot_general(h, w, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (bn,bv)
    if softcap:
        lg = jnp.tanh(lg / softcap) * softcap
    cols = j * bv + jax.lax.broadcasted_iota(jnp.int32, (bn, bv), 1)
    lg = jnp.where(cols < vocab, lg, NEG_INF)            # physical padding

    tgt = tgt_ref[...]                                   # (bn, 1) int32
    hit = (cols == tgt).astype(jnp.float32)
    g_ref[...] = g_ref[...] + (lg * hit).sum(axis=-1, keepdims=True)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, lg.max(axis=-1, keepdims=True))
    l_ref[...] = (l_ref[...] * jnp.exp(m_prev - m_new)
                  + jnp.exp(lg - m_new).sum(axis=-1, keepdims=True))
    m_ref[...] = m_new

    @pl.when(j == nv - 1)
    def _finalize():
        logz = m_ref[...] + jnp.log(jnp.maximum(l_ref[...], 1e-37))
        nll = (logz - g_ref[...]) * valid_ref[...].astype(jnp.float32)
        out_ref[0] = nll[:, 0].astype(out_ref.dtype)


def xent_pallas(hidden: jnp.ndarray, head: jnp.ndarray,
                targets: jnp.ndarray, valid: jnp.ndarray | None = None, *,
                vocab: int = 0, softcap: float = 0.0, block_n: int = 128,
                block_v: int = 512, interpret: bool = False) -> jnp.ndarray:
    """Per-row NLL.  hidden (N, D); head (D, Vp); targets (N,) int32.
    N % block_n == 0 and Vp % block_v == 0 (ops.py pads)."""
    n, d = hidden.shape
    vp = head.shape[1]
    bn = min(block_n, n)
    bv = min(block_v, vp)
    if n % bn or vp % bv:
        raise ValueError(f"(N={n}, Vp={vp}) must tile by ({bn}, {bv})")
    nn, nv = n // bn, vp // bv
    vocab = vocab or vp
    if valid is None:
        valid = jnp.ones((n,), jnp.float32)
    tgt2 = targets.astype(jnp.int32).reshape(n, 1)
    val2 = valid.reshape(n, 1).astype(jnp.float32)

    kernel = functools.partial(_xent_kernel, bn=bn, bv=bv, nv=nv,
                               vocab=vocab, softcap=softcap)
    out = pl.pallas_call(
        kernel,
        grid=(nn, nv),
        in_specs=[
            pl.BlockSpec((1, bn, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((d, bv), lambda i, j: (0, j)),
            pl.BlockSpec((bn, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, 1), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((1, bn), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nn, bn), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((bn, 1), jnp.float32),            # running max
            pltpu.VMEM((bn, 1), jnp.float32),            # running sum
            pltpu.VMEM((bn, 1), jnp.float32),            # gold logit
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="nero_fused_xent",
    )(hidden.reshape(nn, bn, d), head, tgt2, val2)
    return out.reshape(n)
