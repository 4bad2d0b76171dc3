"""Walk a jaxpr with its nested jaxprs, and count the Pallas kernel calls
in it by kernel name.  Shared by tests/test_flash_attention.py and
tests/test_looplm.py (what ``remat`` keeps, PR 29)."""

import collections


def eqns(jaxpr, times: int = 1):
    """Every equation of *jaxpr* with the times it runs, those of nested
    jaxprs (custom_vjp bodies, kernel bodies, branches, loops) included:
    an equation inside a ``scan`` runs once a trip, one inside any other
    nested jaxpr once."""
    for eqn in jaxpr.eqns:
        yield eqn, times
        inner = times * eqn.params["length"] \
            if eqn.primitive.name == "scan" else times
        for param in eqn.params.values():
            for sub in param if isinstance(param, (list, tuple)) else [param]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from eqns(sub, inner)


def kernel_calls(jaxpr) -> dict:
    counts = collections.Counter()
    for eqn, times in eqns(jaxpr):
        if eqn.primitive.name == "pallas_call":
            counts[eqn.params["name"]] += times
    return dict(counts)
