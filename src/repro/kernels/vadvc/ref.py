"""Pure oracle for COSMO vertical advection (Thomas tridiagonal solver).

Faithful to the gridtools `vertical_advection_dycore` benchmark that NERO
implements on the FPGA: an implicit vertical discretization solved with the
Thomas algorithm — forward sweep building/eliminating (ccol, dcol), backward
substitution, and the final tendency update.

Layout: (z, y, x) = (k, j, i).  `wcon` is staggered in i: callers pass
wcon with shape (nz, ny, nx + 1) so both wcon[..., i] and wcon[..., i+1]
exist for every output column i.  In k, the sweep at level k uses wcon[k]
(gav) and wcon[k+1] (gcv), per the staggered vertical grid.

Two oracles are provided:
  * `vadvc_np`   — numpy, python loop over k (the clearest possible spec).
  * `vadvc`      — jnp, lax.scan over k (differentiable/jit path and the
                   reference for the Pallas kernel sweeps).
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

DTR_STAGE = 3.0 / 20.0
BETA_V = 0.0
BET_M = 0.5 * (1.0 - BETA_V)
BET_P = 0.5 * (1.0 + BETA_V)


def vadvc_np(u_stage: np.ndarray, wcon: np.ndarray, u_pos: np.ndarray,
             utens: np.ndarray, utens_stage: np.ndarray) -> np.ndarray:
    """Reference in plain numpy.  All fields (nz, ny, nx); wcon (nz, ny, nx+1).
    Returns the updated utens_stage."""
    u_stage = np.asarray(u_stage, np.float64)
    wcon = np.asarray(wcon, np.float64)
    u_pos = np.asarray(u_pos, np.float64)
    utens = np.asarray(utens, np.float64)
    utens_stage_in = np.asarray(utens_stage, np.float64)
    nz, ny, nx = u_stage.shape

    ccol = np.empty_like(u_stage)
    dcol = np.empty_like(u_stage)
    wl = wcon[:, :, :nx]       # wcon(i)
    wr = wcon[:, :, 1:nx + 1]  # wcon(i+1)

    # ---- forward sweep ----------------------------------------------------
    # k = 0 (no sub-diagonal; gcv from level k+1)
    gcv = 0.25 * (wr[1] + wl[1])
    cs = gcv * BET_M
    ccol[0] = gcv * BET_P
    bcol = DTR_STAGE - ccol[0]
    correction = -cs * (u_stage[1] - u_stage[0])
    dcol[0] = (DTR_STAGE * u_pos[0] + utens[0] + utens_stage_in[0]
               + correction)
    divided = 1.0 / bcol
    ccol[0] *= divided
    dcol[0] *= divided

    # 0 < k < nz-1
    for k in range(1, nz - 1):
        gav = -0.25 * (wr[k] + wl[k])
        gcv = 0.25 * (wr[k + 1] + wl[k + 1])
        as_ = gav * BET_M
        cs = gcv * BET_M
        acol = gav * BET_P
        ccol[k] = gcv * BET_P
        bcol = DTR_STAGE - acol - ccol[k]
        correction = (-as_ * (u_stage[k - 1] - u_stage[k])
                      - cs * (u_stage[k + 1] - u_stage[k]))
        dcol[k] = (DTR_STAGE * u_pos[k] + utens[k] + utens_stage_in[k]
                   + correction)
        divided = 1.0 / (bcol - ccol[k - 1] * acol)
        ccol[k] *= divided
        dcol[k] = (dcol[k] - dcol[k - 1] * acol) * divided

    # k = nz-1 (no super-diagonal)
    k = nz - 1
    gav = -0.25 * (wr[k] + wl[k])
    as_ = gav * BET_M
    acol = gav * BET_P
    bcol = DTR_STAGE - acol
    correction = -as_ * (u_stage[k - 1] - u_stage[k])
    dcol[k] = (DTR_STAGE * u_pos[k] + utens[k] + utens_stage_in[k]
               + correction)
    divided = 1.0 / (bcol - ccol[k - 1] * acol)
    dcol[k] = (dcol[k] - dcol[k - 1] * acol) * divided

    # ---- backward sweep ----------------------------------------------------
    out = np.empty_like(u_stage)
    datac = dcol[nz - 1]
    out[nz - 1] = DTR_STAGE * (datac - u_pos[nz - 1])
    for k in range(nz - 2, -1, -1):
        datac = dcol[k] - ccol[k] * datac
        out[k] = DTR_STAGE * (datac - u_pos[k])
    return out


def _system(u_stage, wcon, u_pos, utens, utens_stage, xp):
    """Tridiagonal system (a, b, c, d) shared by the jnp oracle and the
    residual property check.  Row k: a[k] x[k-1] + b[k] x[k] + c[k] x[k+1]
    = d[k], with a[0] = c[-1] = 0."""
    nz, ny, nx = u_stage.shape
    wl = wcon[:, :, :nx]
    wr = wcon[:, :, 1:nx + 1]
    w = wl + wr
    gav = -0.25 * w                                     # level k
    if xp is np:
        gcv = 0.25 * np.concatenate([w[1:], np.zeros_like(w[-1:])], axis=0)
    else:
        gcv = 0.25 * jnp.concatenate([w[1:], jnp.zeros_like(w[-1:])], axis=0)
    a = gav * BET_P
    if xp is np:
        a[0] = 0.0
    else:
        a = a.at[0].set(0.0)
    c = gcv * BET_P                                     # c[-1] == 0 already
    b = DTR_STAGE - a - c

    du = xp.diff(u_stage, axis=0)                       # u[k+1]-u[k]
    d = DTR_STAGE * u_pos + utens + utens_stage
    if xp is np:
        d[1:] += (gav[1:] * BET_M) * du                 # -as*(u[k-1]-u[k])
        d[:-1] += -(gcv[:-1] * BET_M) * du              # -cs*(u[k+1]-u[k])
    else:
        d = d.at[1:].add((gav[1:] * BET_M) * du)
        d = d.at[:-1].add(-(gcv[:-1] * BET_M) * du)
    return a, b, c, d


def vadvc(u_stage: jnp.ndarray, wcon: jnp.ndarray, u_pos: jnp.ndarray,
          utens: jnp.ndarray, utens_stage: jnp.ndarray) -> jnp.ndarray:
    """jnp oracle via lax.scan (differentiable, jittable)."""
    in_dtype = u_stage.dtype
    f32 = jnp.float32
    u_stage, wcon, u_pos, utens, utens_stage = (
        jnp.asarray(x, f32) for x in (u_stage, wcon, u_pos, utens,
                                      utens_stage))
    a, b, c, d = _system(u_stage, wcon, u_pos, utens, utens_stage, jnp)

    # Thomas forward elimination (a[0] == 0, so a zero carry starts it),
    # then back substitution as a reverse scan (c[-1] == 0 likewise).  The
    # equivalent form that scanned `[::-1]`-reversed slices and
    # concatenated the first level back on came out wrong on the TPU for
    # the lowest 28 of 64 levels.
    def fwd(carry, xs):
        cprev, dprev = carry
        a_k, b_k, c_k, d_k = xs
        denom = 1.0 / (b_k - cprev * a_k)
        out = (c_k * denom, (d_k - dprev * a_k) * denom)
        return out, out

    def bwd(x_next, xs):
        c_k, d_k = xs
        x = d_k - c_k * x_next
        return x, x

    zero = jnp.zeros_like(a[0])
    _, cd = jax.lax.scan(fwd, (zero, zero), (a, b, c, d))
    _, x = jax.lax.scan(bwd, zero, cd, reverse=True)
    out = DTR_STAGE * (x - u_pos)
    return out.astype(in_dtype)


def tridiagonal_residual(u_stage, wcon, u_pos, utens, utens_stage, out):
    """Property check: reconstruct x from `out` and verify A x = d.

    Returns max |A x - d| (float64).  Thomas must actually solve the implicit
    system, independent of any oracle implementation."""
    u_stage, wcon, u_pos, utens, utens_stage, out = (
        np.asarray(v, np.float64)
        for v in (u_stage, wcon, u_pos, utens, utens_stage, out))
    a, b, c, d = _system(u_stage, wcon, u_pos, utens, utens_stage, np)
    x = out / DTR_STAGE + u_pos
    ax = b * x
    ax[1:] += a[1:] * x[:-1]
    ax[:-1] += c[:-1] * x[1:]
    return float(np.max(np.abs(ax - d)))
