"""Walk a traced program: every equation of a jaxpr and of the sub-jaxprs
nested in its equations' params (loop bodies, branches, kernel bodies)."""
from __future__ import annotations

from typing import Iterator

from jax.extend import core as jex_core

__all__ = ["iter_eqns", "pallas_eqns"]


def _sub_jaxprs(v) -> Iterator[jex_core.Jaxpr]:
    if isinstance(v, jex_core.ClosedJaxpr):
        yield v.jaxpr
    elif isinstance(v, jex_core.Jaxpr):
        yield v
    elif isinstance(v, (list, tuple)):
        for x in v:
            yield from _sub_jaxprs(x)


def iter_eqns(jaxpr: jex_core.Jaxpr) -> Iterator[jex_core.JaxprEqn]:
    """Every equation of ``jaxpr``, depth-first through nested jaxprs."""
    for eq in jaxpr.eqns:
        yield eq
        for v in eq.params.values():
            for sub in _sub_jaxprs(v):
                yield from iter_eqns(sub)


def pallas_eqns(jaxpr: jex_core.Jaxpr) -> list:
    """The ``pallas_call`` equations of ``jaxpr``, nested ones included."""
    return [eq for eq in iter_eqns(jaxpr) if eq.primitive.name == "pallas_call"]
