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


def count_indexed(jaxpr, name: str, n: int) -> int:
    """Recursively count eqns of one indexing primitive (``gather``,
    ``scatter``, ...) whose index operand, the eqn's second input, has
    ``n`` rows: the N-long gathers and scatters of a build over N points,
    not the small-table lookups."""
    k = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == name:
            shape = getattr(eqn.invars[1].aval, "shape", ())
            k += bool(shape) and shape[0] == n
        for v in eqn.params.values():
            for sub in _sub_jaxprs(v):
                k += count_indexed(sub, name, n)
    return k
