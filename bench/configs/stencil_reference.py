"""Plain reference of the configurations' time steps, independent of the
program under test (it imports nothing from `repro`).

One member's state is a dict of (nz, ny, nx) arrays: `fields` and
`stage_tens` map field names to arrays, `tens` likewise, and `wcon` is one
array.  The domain is doubly periodic in (y, x).  Every step is written
as the plain numerics the NERO paper names (arXiv 2107.08716, section 2)
and the COSMO/gridtools benchmarks it implements:

* `hdiff`: compound horizontal diffusion, Laplacian -> flux with the COSMO
  flux limiter -> output, at every point of the periodic domain;
* `hadv`: first-order upwind (donor-cell) horizontal advection;
* `vadvc`: implicit vertical advection, a tridiagonal system per column
  solved by the Thomas algorithm, returning the updated stage tendency.

`dycore` is vadvc -> point-wise update -> hdiff per field; `chain` puts
hadv in front of it.  Both step in the dtype of the state they are given,
so the same code computed in bfloat16 is a lower-precision control.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

DTR_STAGE = 3.0 / 20.0
BET_M = 0.5
BET_P = 0.5


def _at(a, dj, di):
    """Value at (j + dj, i + di) over the last two axes, periodic."""
    return jnp.roll(a, (-dj, -di), axis=(-2, -1))


def hdiff(f, coeff):
    lap = ((_at(f, 0, -1) + _at(f, 0, 1) + _at(f, -1, 0) + _at(f, 1, 0))
           - 4.0 * f)
    flx = _at(lap, 0, 1) - lap
    flx_m = lap - _at(lap, 0, -1)
    fly = _at(lap, 1, 0) - lap
    fly_m = lap - _at(lap, -1, 0)
    zero = jnp.zeros_like(f)
    flx = jnp.where(flx * (_at(f, 0, 1) - f) > 0, zero, flx)
    flx_m = jnp.where(flx_m * (f - _at(f, 0, -1)) > 0, zero, flx_m)
    fly = jnp.where(fly * (_at(f, 1, 0) - f) > 0, zero, fly)
    fly_m = jnp.where(fly_m * (f - _at(f, -1, 0)) > 0, zero, fly_m)
    return f - coeff * ((flx - flx_m) + (fly - fly_m))


def hadv(f, cfl):
    return f - cfl * ((f - _at(f, -1, 0)) + (f - _at(f, 0, -1)))


def vadvc(u, wcon, utens, utens_stage):
    """Updated stage tendency of field `u` (also the solve's u_pos).
    wcon is staggered in x: column i uses wcon[i] and wcon[i + 1]."""
    w = wcon + _at(wcon, 0, 1)
    gav = -0.25 * w                                       # level k
    gcv = 0.25 * jnp.concatenate([w[1:], jnp.zeros_like(w[:1])])  # k + 1
    a = (gav * BET_P).at[0].set(0.0)
    c = gcv * BET_P
    b = DTR_STAGE - a - c
    du = u[1:] - u[:-1]                                   # u[k+1] - u[k]
    d = DTR_STAGE * u + utens + utens_stage
    d = d.at[1:].add((gav[1:] * BET_M) * du)
    d = d.at[:-1].add(-(gcv[:-1] * BET_M) * du)

    def forward(carry, abcd):
        c_prev, d_prev = carry
        a_k, b_k, c_k, d_k = abcd
        inv = 1.0 / (b_k - c_prev * a_k)
        out = (c_k * inv, (d_k - d_prev * a_k) * inv)
        return out, out

    def backward(x_next, cd):
        c_k, d_k = cd
        x = d_k - c_k * x_next
        return x, x

    zero = jnp.zeros_like(u[0])
    _, cd = jax.lax.scan(forward, (zero, zero), (a, b, c, d))
    _, x = jax.lax.scan(backward, zero, cd, reverse=True)
    return DTR_STAGE * (x - u)


def _dycore_field(f, wcon, utens, stage, coeff, dt):
    stage = vadvc(f, wcon, utens, stage)
    return hdiff(f + dt * stage, coeff), stage


def dycore(state, coeff, dt):
    """One fused compound step: per field vadvc -> f + dt*stage -> hdiff."""
    fields, stage = {}, {}
    for n, f in state["fields"].items():
        fields[n], stage[n] = _dycore_field(f, state["wcon"],
                                            state["tens"][n],
                                            state["stage_tens"][n], coeff, dt)
    return dict(state, fields=fields, stage_tens=stage)


def chain(state, coeff, dt):
    """hadv_upwind -> vadvc_update -> hdiff; the chain's upwind CFL is the
    program's shared `coeff`."""
    fields, stage = {}, {}
    for n, f in state["fields"].items():
        fields[n], stage[n] = _dycore_field(hadv(f, coeff), state["wcon"],
                                            state["tens"][n],
                                            state["stage_tens"][n], coeff, dt)
    return dict(state, fields=fields, stage_tens=stage)
