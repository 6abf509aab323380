"""Shared jaxpr-inspection helpers for the launch/sort-count tests."""


def _sub_jaxprs(v):
    """Jaxprs nested in one eqn param: a Jaxpr has ``.eqns``, a
    ClosedJaxpr carries one under ``.jaxpr``."""
    for sub in (v if isinstance(v, (list, tuple)) else [v]):
        if hasattr(sub, "eqns"):
            yield sub
        elif hasattr(getattr(sub, "jaxpr", None), "eqns"):
            yield sub.jaxpr


def count_eqns(jaxpr, name: str) -> int:
    """Recursively count eqns of one primitive in a jaxpr (incl. sub-jaxprs)."""
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == name:
            n += 1
        for v in eqn.params.values():
            for sub in _sub_jaxprs(v):
                n += count_eqns(sub, name)
    return n


def count_pallas_calls(jaxpr) -> int:
    """Recursively count pallas_call eqns in a jaxpr (incl. sub-jaxprs)."""
    return count_eqns(jaxpr, "pallas_call")


def count_sorts(jaxpr) -> int:
    """Recursively count sort eqns in a jaxpr (incl. sub-jaxprs)."""
    return count_eqns(jaxpr, "sort")
