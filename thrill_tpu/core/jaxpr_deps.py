"""Which inputs of a jaxpr each of its outputs can depend on.

Shared by api/loop.py (is a fetched plan value tainted by the carry?)
and api/fusion.py (which arguments of a stitched chain does an index
plan read?).
"""

from __future__ import annotations

from typing import List

# call-like primitives whose sub-jaxpr maps eqn invars to outvars
# one-to-one, so reachability may recurse instead of union-ing all
# inputs into all outputs. Loops/conds (scan, while, cond) are NOT
# here on purpose: their iteration semantics mix operands across
# rounds, so they keep the conservative union.
_CALL_PRIMS = frozenset({"pjit", "closed_call", "core_call", "xla_call",
                         "custom_jvp_call", "custom_vjp_call",
                         "remat", "checkpoint", "shard_map"})


def output_deps(jaxpr) -> List[frozenset]:
    """For each jaxpr output, the set of INVAR indices it may depend
    on — a conservative over-approximation (per-equation union, with
    recursion into call-like sub-jaxprs so a ``pjit``/``shard_map``
    wrapper does not collapse the whole program into one equation)."""
    deps = {v: frozenset([i]) for i, v in enumerate(jaxpr.invars)}

    def get(atom):
        if hasattr(atom, "val"):           # Literal
            return frozenset()
        return deps.get(atom, frozenset())  # constvars -> empty

    for eqn in jaxpr.eqns:
        sub = None
        if eqn.primitive.name in _CALL_PRIMS:
            p = eqn.params.get("jaxpr") or eqn.params.get("call_jaxpr")
            if p is not None:
                inner = getattr(p, "jaxpr", p)   # ClosedJaxpr -> Jaxpr
                if len(inner.invars) == len(eqn.invars):
                    sub = inner
        if sub is not None:
            inner_out = output_deps(sub)
            in_sets = [get(a) for a in eqn.invars]
            for ov, od in zip(eqn.outvars, inner_out):
                s = frozenset()
                for k in od:
                    s |= in_sets[k]
                deps[ov] = s
            continue
        u = frozenset()
        for a in eqn.invars:
            u |= get(a)
        for ov in eqn.outvars:
            deps[ov] = u
    return [get(o) for o in jaxpr.outvars]
