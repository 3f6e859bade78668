"""Distributed dycore primitives: halo exchange + sharding utilities.

This is NERO's scale-out story made real (paper §5: "HBM provides an
attractive solution for scale-out computation" with one memory channel per
PE): every chip owns an (ny/Py, nx/Px) slab of the horizontal domain in its
own HBM; the compound stencils run chip-locally out of VMEM; the only
communication is a circular halo exchange (`jax.lax.ppermute` over the mesh
axes).  Vertical columns are never split (vadvc's z dependency), matching
the paper's PE design.

The strategy that *uses* these primitives — which stencil op runs
chip-locally, which variant, how deep each operand's halo is, what rides
the wire at which dtype — is resolved by the plan API
(`weather/program.py::compile` over the StencilOp registry,
`weather/stencil_ops.py`); the distributed lowerings there compose:

* `_exchange` — per-operand circular exchange (the per-field paths);
* `_exchange_packed` — the stacked RAGGED exchange: several tensors with
  PER-TENSOR (and per-SIDE) halo depths share one flattened wire buffer
  per direction, so the collective count stays at most one `ppermute` pair
  per mesh direction per round no matter how many operands ride or how
  ragged their depths are.  Depths come straight from the registered op's
  declared footprint and may be ZERO per side — a direction nothing rides
  is elided entirely (vadvc's right-only wcon column is ONE ppermute);
  the dycore's `wcon` ships its `+1` staggering x-column to the RIGHT
  side only (`w[c] = wcon[c] + wcon[c+1]` needs the right neighbor,
  never the left — the left pad's extra column was provably unread);
* `_staggered_w` / `_right_column` — the x-staggered velocity build;
* `_local_hdiff` / `_local_vadvc` — exchanged per-kernel local stencils
  (the unfused oracle's distributed form);
* `shard_state` — placing a `WeatherState` onto the mesh.

The legacy `make_distributed_step(...)` flag-soup shim is gone (retired
ROADMAP item): build a `StencilProgram`/`DycoreProgram` and call
`repro.weather.program.compile(program, mesh=mesh)`.  Ensemble members
ride the "pod" axis of the multi-pod mesh — see docs/architecture.md
("Scale-out: domain decomposition and ensemble pods").
"""

from __future__ import annotations

from typing import Iterable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.kernels.hdiff import ref as hdiff_ref
from repro.kernels.vadvc import ref as vadvc_ref
from repro.weather.fields import WeatherState
from repro.weather.dycore import HALO


def _exchange(f: jnp.ndarray, axis_name: str, n: int, halo: int,
              dim: int) -> jnp.ndarray:
    """Circular halo exchange along `dim` over mesh axis `axis_name`.

    Returns f extended by `halo` on both sides of `dim`.  With n == 1 this
    degenerates to periodic wrap-padding (no communication).  `halo` must
    not exceed the local extent (a deeper exchange would need neighbors-of-
    neighbors data — callers check and raise)."""
    def take(a, sl):
        idx = [slice(None)] * a.ndim
        idx[dim] = sl
        return a[tuple(idx)]

    lo = take(f, slice(0, halo))          # my first rows -> neighbor below
    hi = take(f, slice(-halo, None))      # my last rows  -> neighbor above
    if n == 1:
        top, bot = hi, lo
    else:
        fwd = [(i, (i + 1) % n) for i in range(n)]
        bwd = [(i, (i - 1) % n) for i in range(n)]
        top = jax.lax.ppermute(hi, axis_name, perm=fwd)   # from rank-1
        bot = jax.lax.ppermute(lo, axis_name, perm=bwd)   # from rank+1
    return jnp.concatenate([top, f, bot], axis=dim)


def _exchange_packed(parts, axis_name: str, n: int, dim: int,
                     wire_dtype=None):
    """Circular halo exchange along `dim` for several tensors with
    PER-TENSOR — and per-SIDE — halo depths, packed into one flattened
    wire buffer per direction: exactly one `ppermute` pair regardless of
    operand count or depth raggedness.

    `parts` is a sequence of `(tensor, depth)` where `depth` is either an
    int (symmetric) or a `(lo_depth, hi_depth)` pair: the tensor comes
    back extended by `lo_depth` on the LOW side of `dim` (received from
    the lower-index neighbor) and `hi_depth` on the HIGH side (received
    from the upper-index neighbor).  This is how `wcon` ships its extra
    staggering column to the right side ONLY — `(k·HALO, k·HALO + 1)` —
    without forcing the whole stacked exchange one column deeper, and
    without wasting a never-read column on the left pad.

    Depths may be ZERO per side (and per operand): a zero side ships
    nothing for that operand, and when a direction's packed buffer is
    empty for EVERY operand the `ppermute` for that direction is elided
    entirely.  That is how a registered stencil op's declared footprint
    (`weather/stencil_ops.py`) lowers directly to the minimal collective
    set — e.g. vadvc's `(0, 1)` wcon ride is ONE ppermute (the right
    staggering column), not a pair.

    `wire_dtype` (e.g. bf16) casts the packed buffer before each
    `ppermute` and restores each tensor's dtype on arrival — half the
    wire bytes, rounding confined to the received halo ring.

    With n == 1 this degenerates to periodic wrap-padding (no
    communication, no cast)."""
    def take_last(a, d):
        idx = [slice(None)] * a.ndim
        # slice(-0, None) would be the WHOLE tensor; zero depth is empty.
        idx[dim] = slice(-d, None) if d else slice(0, 0)
        return a[tuple(idx)]

    def take_first(a, d):
        idx = [slice(None)] * a.ndim
        idx[dim] = slice(0, d)
        return a[tuple(idx)]

    depths = []
    for _, h in parts:
        lo_h, hi_h = (h, h) if isinstance(h, int) else h
        if lo_h < 0 or hi_h < 0:
            raise ValueError(f"packed-exchange depth {h!r} must be >= 0 "
                             f"on both sides")
        depths.append((lo_h, hi_h))
    # The LOW pad is the lower neighbor's LAST lo_h rows (forward ride);
    # the HIGH pad is the upper neighbor's FIRST hi_h rows (backward ride).
    hi_parts = [take_last(t, lo_h)
                for (t, _), (lo_h, _) in zip(parts, depths)]
    lo_parts = [take_first(t, hi_h)
                for (t, _), (_, hi_h) in zip(parts, depths)]

    def ride(xs, perm):
        """One packed ppermute of `xs`; elided when nothing rides."""
        if n == 1 or all(x.size == 0 for x in xs):
            return xs

        buf = jnp.concatenate([x.reshape(-1) for x in xs])
        if wire_dtype is not None:
            buf = buf.astype(wire_dtype)
        buf = jax.lax.ppermute(buf, axis_name, perm=perm)
        out, off = [], 0
        for x in xs:
            seg = buf[off:off + x.size]
            out.append(seg.reshape(x.shape).astype(x.dtype))
            off += x.size
        return out

    fwd = [(i, (i + 1) % n) for i in range(n)]
    bwd = [(i, (i - 1) % n) for i in range(n)]
    top = ride(hi_parts, fwd)
    bot = ride(lo_parts, bwd)
    return [jnp.concatenate([t_, t, b_], axis=dim)
            for (t, _), t_, b_ in zip(parts, top, bot)]


def _right_column(wcon: jnp.ndarray, ax_x: str, nx_shards: int) -> jnp.ndarray:
    """The x-staggered neighbor of the slab's last column: the x-neighbor
    shard's first column (periodic 1-column exchange)."""
    if nx_shards == 1:
        return wcon[..., :1]
    bwd = [(i, (i - 1) % nx_shards) for i in range(nx_shards)]
    return jax.lax.ppermute(wcon[..., :1], ax_x, perm=bwd)


def _staggered_w(wcon: jnp.ndarray, ax_x: str, nx_shards: int) -> jnp.ndarray:
    """w = wcon_i + wcon_{i+1} on the local slab (see _right_column)."""
    right = _right_column(wcon, ax_x, nx_shards)
    return wcon + jnp.concatenate([wcon[..., 1:], right], axis=-1)


def _local_hdiff(f: jnp.ndarray, coeff: float, ax_y: str, ax_x: str,
                 ny_shards: int, nx_shards: int) -> jnp.ndarray:
    """f: (E, nz, ly, lx) local slab -> diffused slab."""
    e, nz, ly, lx = f.shape
    g = _exchange(f, ax_y, ny_shards, HALO, dim=2)
    g = _exchange(g, ax_x, nx_shards, HALO, dim=3)
    out = hdiff_ref.hdiff(g.reshape(e * nz, ly + 2 * HALO, lx + 2 * HALO),
                          coeff=coeff)
    out = out.reshape(e, nz, ly + 2 * HALO, lx + 2 * HALO)
    return out[:, :, HALO:HALO + ly, HALO:HALO + lx]


def _local_vadvc(u_stage, wcon, u_pos, utens, utens_stage, ax_x, nx_shards):
    """All (E, nz, ly, lx); staggered wcon column fetched from x-neighbor."""
    wcon_s = jnp.concatenate(
        [wcon, _right_column(wcon, ax_x, nx_shards)], axis=-1)
    # vmap over ensemble; fields already (nz, ly, lx) per member.
    out = jax.vmap(vadvc_ref.vadvc)(u_stage, wcon_s, u_pos, utens,
                                    utens_stage)
    return out


def shard_state(state: WeatherState, mesh: Mesh, spec: P) -> WeatherState:
    sharding = NamedSharding(mesh, spec)
    return jax.tree.map(lambda a: jax.device_put(a, sharding), state)


def gather_state(state: WeatherState) -> WeatherState:
    """Pull a (possibly sharded) state fully to host as numpy arrays —
    the unsharded-logical form every mesh can reshard from.  This is the
    reshard pivot of the elastic failover/restore path: gather on the old
    mesh, `shard_state` on the new one."""
    return jax.tree.map(lambda a: np.asarray(jax.device_get(a)), state)


def _mesh_from(devices, shape: Tuple[int, int], axes) -> Mesh:
    n = shape[0] * shape[1]
    return Mesh(np.asarray(devices[:n]).reshape(shape), tuple(axes),
                axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def failover_meshes(devices, grids: Iterable[Tuple[int, int, int]],
                    axes=("data", "model"),
                    like: Optional[Tuple[int, int]] = None) -> List[Mesh]:
    """Candidate meshes over surviving `devices`, best first.

    Every candidate's (py, px) divides EVERY grid in `grids` (ny over py,
    nx over px) — one mesh must carry every lane.  Ordering: more devices
    first; then shapes whose sharded-axis PATTERN matches `like` (the
    dying mesh's (py, px)).  The pattern preference is a bitwise-identity
    matter, not cosmetics: collapsing a sharded axis to 1 shard switches
    that axis from halo-exchange to wrap-padding lowering, which changes
    result bits for ops that are not sharding-transparent — whereas
    *shrinking* a sharded axis (4→2 shards) provably keeps bits (see
    tests/test_mesh_failover.py).  A caller walks the list and takes the
    first mesh its plans compile on."""
    devices = list(devices)
    grids = list(grids)
    cands: List[Tuple[int, int]] = []
    for n in range(len(devices), 0, -1):
        for py in range(n, 0, -1):
            if n % py:
                continue
            px = n // py
            if all(ny % py == 0 and nx % px == 0 for _, ny, nx in grids):
                cands.append((py, px))

    def score(pp):
        py, px = pp
        match = 0
        if like is not None:
            match = ((py > 1) == (like[0] > 1)) + ((px > 1) == (like[1] > 1))
        return (-(py * px), -match, -py)

    return [_mesh_from(devices, pp, axes)
            for pp in sorted(cands, key=score)]
