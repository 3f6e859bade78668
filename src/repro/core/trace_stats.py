"""Count primitives in traced jaxprs — launch/collective accounting.

The whole-state dycore's contract is structural, not just numerical: ONE
`pallas_call` per step, ONE `ppermute` pair per mesh direction per k-step
round.  Those invariants are asserted by counting primitive equations in
the traced jaxpr (recursing through pjit/scan/shard_map/cond sub-jaxprs),
which works on any backend — including CPU, where Pallas interpret-mode
never lowers to a custom call that HLO-level counting could find.
"""

from __future__ import annotations

from typing import Any, Dict, List, Tuple


def _sub_jaxprs(eqn) -> list:
    subs = []
    for v in eqn.params.values():
        vs = v if isinstance(v, (tuple, list)) else (v,)
        for x in vs:
            if hasattr(x, "jaxpr"):        # ClosedJaxpr
                subs.append(x.jaxpr)
            elif hasattr(x, "eqns"):       # raw Jaxpr
                subs.append(x)
    return subs


def count_primitive(jaxpr, name: str) -> int:
    """Occurrences of primitive `name` in `jaxpr`, recursing into every
    sub-jaxpr (pjit, scan, while, cond branches, shard_map, ...).  A scan
    body counts ONCE regardless of trip count — this counts distinct
    launches/collectives in the program text, i.e. per-iteration cost."""
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)   # accept ClosedJaxpr
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == name:
            n += 1
        for sub in _sub_jaxprs(eqn):
            n += count_primitive(sub, name)
    return n


def launch_and_collective_counts(jaxpr) -> Dict[str, int]:
    """The two structural costs of a distributed dycore round: Pallas
    launches and ppermute collectives in the traced program (scan bodies
    counted once — i.e. per-round cost)."""
    return {"pallas_call": count_primitive(jaxpr, "pallas_call"),
            "ppermute": count_primitive(jaxpr, "ppermute")}


def assert_kstep_structure(jaxpr, *, pallas_calls: int = 1,
                           collectives: int = 4) -> Dict[str, int]:
    """Assert the k-step round's structural win: exactly ONE `pallas_call`
    (the in-kernel k-step scan — no launch per local step) and one
    `ppermute` pair per mesh direction (4 collectives) per round.  Returns
    the counts; raises AssertionError naming the violated invariant."""
    counts = launch_and_collective_counts(jaxpr)
    if counts["pallas_call"] != pallas_calls:
        raise AssertionError(
            f"k-step round launches {counts['pallas_call']} Pallas kernels, "
            f"expected {pallas_calls} (the round must be ONE launch)")
    if counts["ppermute"] != collectives:
        raise AssertionError(
            f"k-step round issues {counts['ppermute']} ppermutes, expected "
            f"{collectives} (one pair per mesh direction per round)")
    return counts


def assert_plan_structure(jaxpr, report: Dict[str, Any]) -> Dict[str, int]:
    """Assert a traced plan round matches the plan's OWN `report()`: the
    modeled `pallas_calls_per_round` / `collectives_per_round` must be the
    program text's actual primitive counts (a plan whose report lies about
    its structure is a planner bug).  Returns the counts."""
    counts = launch_and_collective_counts(jaxpr)
    for key, prim in (("pallas_calls_per_round", "pallas_call"),
                      ("collectives_per_round", "ppermute")):
        want = report.get(key)
        if want is not None and counts[prim] != want:
            raise AssertionError(
                f"plan.report()[{key!r}] = {want} but the traced round "
                f"contains {counts[prim]} {prim} eqns")
    return counts


def primitive_counts(jaxpr, kernels: bool = True) -> Dict[str, int]:
    """Histogram of every primitive in `jaxpr` (recursive, scan bodies
    counted once).  `kernels=False` counts each `pallas_call` as one
    primitive and leaves its kernel body out: what the program does
    around its launches."""
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)
    out: Dict[str, int] = {}

    def walk(j: Any) -> None:
        for eqn in j.eqns:
            out[eqn.primitive.name] = out.get(eqn.primitive.name, 0) + 1
            if kernels or eqn.primitive.name != "pallas_call":
                for sub in _sub_jaxprs(eqn):
                    walk(sub)

    walk(jaxpr)
    return out


def scan_bodies(jaxpr) -> List[Tuple[int, Any]]:
    """`(unroll, body)` of each outermost `scan` in `jaxpr`, in program
    order, outside kernel bodies: the body is one iteration's jaxpr, which
    the compiled loop repeats `unroll` times per trip."""
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)
    out: List[Tuple[int, Any]] = []

    def walk(j: Any) -> None:
        for eqn in j.eqns:
            if eqn.primitive.name == "scan":
                out.append((eqn.params["unroll"], eqn.params["jaxpr"].jaxpr))
            elif eqn.primitive.name != "pallas_call":
                for sub in _sub_jaxprs(eqn):
                    walk(sub)

    walk(jaxpr)
    return out


def pallas_call_names(jaxpr) -> Tuple[str, ...]:
    """The `name` of each distinct `pallas_call` in `jaxpr`, in program
    order (recursing like `count_primitive`): the names the kernels carry
    in a device profile, where an instance reads `<name>.<n>`."""
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)
    out: list = []

    def walk(j: Any) -> None:
        for eqn in j.eqns:
            if (eqn.primitive.name == "pallas_call"
                    and eqn.params["name"] not in out):
                out.append(eqn.params["name"])
            for sub in _sub_jaxprs(eqn):
                walk(sub)

    walk(jaxpr)
    return tuple(out)
