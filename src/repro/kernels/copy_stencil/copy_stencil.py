"""Pallas copy stencil: one VMEM-blocked stream per grid step ("PE")."""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _copy_kernel(in_ref, out_ref):
    out_ref[...] = in_ref[...]


def copy_pallas(src: jnp.ndarray, tr: int = 256,
                interpret: bool = False) -> jnp.ndarray:
    """src: (rows, cols); rows % tr == 0.  Each grid step streams one
    (tr, cols) window HBM->VMEM->HBM, double-buffered by the pipeline."""
    rows, cols = src.shape
    if rows % tr:
        raise ValueError(f"rows={rows} % tr={tr} != 0")
    spec = pl.BlockSpec((tr, cols), lambda r: (r, 0))
    fn = pl.pallas_call(
        _copy_kernel,
        grid=(rows // tr,),
        in_specs=[spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(src.shape, src.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name="nero_copy",
    )
    return fn(src)
