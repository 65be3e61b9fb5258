"""Shared jaxpr walker for the structural pins (not a test module).

Walks a jaxpr and every jaxpr nested in its equations' params (``pjit``,
``shard_map``, ``scan``/``while`` bodies, ``pallas_call`` kernels), on the
public ``jax.extend.core`` types.  The batteries import it as a sibling
module: they run as scripts from ``tests/``, and pytest puts ``tests/`` on
``sys.path`` for the in-process test files."""
from __future__ import annotations

from typing import Iterator

from jax.extend import core as jcore


def iter_eqns(jaxpr) -> Iterator:
    """Every equation of ``jaxpr`` (a ``Jaxpr`` or ``ClosedJaxpr``) and of
    the jaxprs nested in its params, depth first."""
    if isinstance(jaxpr, jcore.ClosedJaxpr):
        jaxpr = jaxpr.jaxpr
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            yield from _nested(v)


def _nested(v) -> Iterator:
    if isinstance(v, (jcore.ClosedJaxpr, jcore.Jaxpr)):
        yield from iter_eqns(v)
    elif isinstance(v, (list, tuple)):
        for x in v:
            yield from _nested(x)


def count_primitive(jaxpr, name: str) -> int:
    """How many equations, nested ones included, apply primitive ``name``."""
    return sum(eqn.primitive.name == name for eqn in iter_eqns(jaxpr))
