"""Loop-aware HLO cost analyzer vs XLA's own cost_analysis.

Validation strategy (the analyzer is what makes scanned dry-run cells give
exact roofline terms):
  1. multipliers forced to 1  -> must match compiled.cost_analysis(),
  2. scanned fn, real multipliers -> must match the fully-unrolled compile,
  3. trip counts parsed from backend_config must equal the scan length,
  4. in-loop collectives are multiplied (the term XLA drops entirely).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import hlo_cost


def _cost(compiled) -> dict:
    return compiled.cost_analysis()


def _body(c, _):
    (x,) = c
    return (jnp.tanh(x @ x),), None


def _scanned(x, n):
    (y,), _ = jax.lax.scan(_body, (x,), None, length=n)
    return y


def _unrolled(x, n):
    for _ in range(n):
        x = jnp.tanh(x @ x)
    return x


@pytest.fixture(scope="module")
def compiled_pair():
    spec = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    cs = jax.jit(lambda x: _scanned(x, 12)).lower(spec).compile()
    cu = jax.jit(lambda x: _unrolled(x, 12)).lower(spec).compile()
    return cs, cu


def test_multiplier_one_matches_xla(compiled_pair):
    cs, _ = compiled_pair
    xla = _cost(cs)
    mine = hlo_cost.analyze_text(cs.as_text(), loop_multipliers=False)
    assert mine.flops == pytest.approx(xla["flops"], rel=0.02)
    assert mine.bytes_accessed == pytest.approx(xla["bytes accessed"],
                                                rel=0.05)
    assert mine.transcendentals == pytest.approx(
        xla.get("transcendentals", 0.0), rel=0.02)


def test_loop_aware_matches_unrolled(compiled_pair):
    cs, cu = compiled_pair
    xla_unrolled = _cost(cu)
    mine = hlo_cost.analyze_text(cs.as_text())
    assert mine.while_trip_counts == [12]
    assert mine.flops == pytest.approx(xla_unrolled["flops"], rel=0.02)
    assert mine.bytes_accessed == pytest.approx(
        xla_unrolled["bytes accessed"], rel=0.05)


def test_nested_scan_multiplies_both_levels():
    def inner(c, _):
        return jnp.sin(c * 2.0), None

    def outer(c, _):
        y, _ = jax.lax.scan(inner, c, None, length=5)
        return y @ y, None

    def f(x):
        y, _ = jax.lax.scan(outer, x, None, length=7)
        return y

    spec = jax.ShapeDtypeStruct((32, 32), jnp.float32)
    c = jax.jit(f).lower(spec).compile()
    mine = hlo_cost.analyze_text(c.as_text())
    assert sorted(mine.while_trip_counts) == [5, 7]
    # 7 outer iterations x one 32x32x32 matmul each
    assert mine.flops >= 7 * 2 * 32 ** 3
    # 35 sin applications of 1024 elements
    assert mine.transcendentals == pytest.approx(35 * 1024, rel=0.02)


_COLL_SNIPPET = r"""
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.core import hlo_cost

mesh = jax.make_mesh((4,), ("d",))

def body(c, _):
    return jax.lax.psum(c, "d") * 0.5, None

def f(x):
    y, _ = jax.lax.scan(body, x, None, length=9)
    return y

from repro.compat import shard_map
smap = shard_map(f, mesh, in_specs=P("d"), out_specs=P(None))
spec = jax.ShapeDtypeStruct((8, 128), jnp.float32)
c = jax.jit(smap).lower(spec).compile()
mine = hlo_cost.analyze_text(c.as_text())
ar = mine.collective_bytes.get("all-reduce", 0.0)
# 9 iterations x per-device (2,128) f32 shard = 9 x 1024 B
assert ar == 9 * 2 * 128 * 4, mine.collective_bytes
print("COLL_OK")
"""


def test_inloop_collective_bytes_multiplied():
    """In-loop collectives get the trip-count multiplier (XLA's own
    cost_analysis misses them entirely).  Runs with 4 forced host devices."""
    import os
    import subprocess
    import sys
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = "src"
    r = subprocess.run([sys.executable, "-c", _COLL_SNIPPET], env=env,
                       capture_output=True, text=True, timeout=600,
                       cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))))
    assert "COLL_OK" in r.stdout, r.stderr[-2000:]


def test_dot_general_batched_flops():
    def f(a, b):
        return jnp.einsum("bij,bjk->bik", a, b)

    sa = jax.ShapeDtypeStruct((4, 16, 32), jnp.float32)
    sb = jax.ShapeDtypeStruct((4, 32, 8), jnp.float32)
    c = jax.jit(f).lower(sa, sb).compile()
    mine = hlo_cost.analyze_text(c.as_text())
    xla = _cost(c)
    assert mine.flops == pytest.approx(xla["flops"], rel=0.02)
    assert mine.flops == pytest.approx(2 * 4 * 16 * 32 * 8, rel=0.02)


def test_zero_byte_scope_credits_bytes_not_flops():
    """Kernel-credit accounting: ops under a named scope (and everything
    they call, incl. scan bodies whose metadata XLA drops) charge zero HBM
    bytes; FLOPs are never zeroed."""
    def body(c, _):
        with jax.named_scope("hot_kernel"):
            c = jnp.tanh(c @ c)
        return c, None

    def f(x):
        y, _ = jax.lax.scan(body, x, None, length=6)
        return y * 2.0

    spec = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    c = jax.jit(f).lower(spec).compile()
    base = hlo_cost.analyze_text(c.as_text())
    cred = hlo_cost.analyze_text(c.as_text(),
                                 zero_byte_scopes=("hot_kernel",))
    assert cred.flops == base.flops
    assert cred.transcendentals == base.transcendentals
    assert cred.bytes_fused < base.bytes_fused * 0.5
    assert cred.bytes_accessed < base.bytes_accessed
