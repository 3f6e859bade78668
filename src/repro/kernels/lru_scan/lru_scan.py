"""Pallas TPU kernel for the first-order linear recurrence (RG-LRU sweep).

NERO's vadvc PE design transplanted to the time axis: channels are the
parallel "columns" (each grid column block is a PE with its own HBM
stream), time is the sequential sweep.  The running state h lives in VMEM
scratch and persists across the sequential grid axis — the Pallas idiom for
carry-over-grid (TPU grids execute sequentially over the last dimension).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _lru_kernel(a_ref, b_ref, out_ref, h_ref, *, tt: int):
    t_idx = pl.program_id(1)

    @pl.when(t_idx == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    a = a_ref[...].astype(jnp.float32)       # (tt, tc)
    b = b_ref[...].astype(jnp.float32)

    def body(i, h):
        h = a[i] * h + b[i]
        out_ref[pl.ds(i, 1), :] = h[None].astype(out_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, tt, body, h_ref[0])
    h_ref[...] = h[None]


def lru_scan_pallas(a: jnp.ndarray, b: jnp.ndarray, tt: int = 32,
                    tc: int = 128, interpret: bool = False) -> jnp.ndarray:
    """a, b: (T, C); T % tt == 0, C % tc == 0."""
    t, c = a.shape
    if t % tt or c % tc:
        raise ValueError(f"(T={t}, C={c}) must tile by (tt={tt}, tc={tc})")
    spec = pl.BlockSpec((tt, tc), lambda ci, ti: (ti, ci))
    fn = pl.pallas_call(
        functools.partial(_lru_kernel, tt=tt),
        grid=(c // tc, t // tt),
        in_specs=[spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(a.shape, a.dtype),
        scratch_shapes=[pltpu.VMEM((1, tc), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="nero_lru_scan",
    )
    return fn(a, b)
