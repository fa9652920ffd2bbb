"""Shared recursive jaxpr walker (flcheck's traversal core).

One traversal, many callers: the flcheck rules
(``repro.analysis.rules``) and any future jaxpr-shaped question walk
programs through :func:`iter_sites` instead of keeping private
recursions.  Each equation is yielded as an :class:`EqnSite` carrying

* ``multiplier`` — the product of the enclosing ``lax.scan`` lengths
  (the static execution count of the equation; ``while`` bodies have no
  static trip count and contribute x1, but appear in ``path``), and
* ``path`` — the enclosing higher-order primitive names (``("scan",)``,
  ``("scan", "cond")``, ...), so a rule can ask "is this equation
  inside a fused round scan?" without re-walking.

Sub-jaxprs are discovered structurally (any eqn param that is a
``ClosedJaxpr``/``Jaxpr``, or a tuple/list of them), which covers
``scan``/``while``/``cond``/``pjit``/``custom_vjp``/... without a
per-primitive table.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Iterable, Iterator, Tuple


# Primitives that call back into the host from inside a traced program.
# Any of these inside a fused block is a device->host edge the
# round-engine contract forbids (DESIGN.md §6/§8).
CALLBACK_PRIMITIVES = ("pure_callback", "io_callback", "debug_callback",
                       "outside_call", "host_callback_call")

# Loop-shaped higher-order primitives: an equation whose ``path``
# crosses one of these runs repeatedly per dispatch.
LOOP_PRIMITIVES = ("scan", "while", "fori", "map")


@dataclasses.dataclass(frozen=True)
class EqnSite:
    """One equation plus where the walk found it."""
    eqn: Any                      # jax.core.JaxprEqn
    multiplier: int               # product of enclosing static scan lengths
    path: Tuple[str, ...]         # enclosing higher-order primitive names

    @property
    def primitive(self) -> str:
        return self.eqn.primitive.name

    @property
    def in_loop(self) -> bool:
        return any(p in LOOP_PRIMITIVES for p in self.path)


def _as_jaxpr(obj):
    """ClosedJaxpr -> Jaxpr; Jaxpr -> itself; else None."""
    inner = getattr(obj, "jaxpr", None)
    if inner is not None and hasattr(inner, "eqns"):
        return inner
    if hasattr(obj, "eqns"):
        return obj
    return None


def sub_jaxprs(eqn) -> Iterator[Tuple[Any, int]]:
    """Yield ``(jaxpr, multiplier)`` for each sub-jaxpr of ``eqn``.

    The multiplier is the equation's static repeat count for that body:
    ``scan`` bodies repeat ``length`` times; everything else (cond
    branches, while bodies, pjit calls) contributes x1.
    """
    is_scan = eqn.primitive.name == "scan"
    for key, val in eqn.params.items():
        for sub in (val if isinstance(val, (tuple, list)) else (val,)):
            j = _as_jaxpr(sub)
            if j is None:
                continue
            mult = int(eqn.params.get("length", 1)) \
                if is_scan and key == "jaxpr" else 1
            yield j, mult


def iter_sites(jaxpr, multiplier: int = 1,
               path: Tuple[str, ...] = ()) -> Iterator[EqnSite]:
    """Depth-first walk over every equation of ``jaxpr`` (a ``Jaxpr`` or
    ``ClosedJaxpr``), descending into sub-jaxprs with accumulated
    multipliers and primitive paths."""
    j = _as_jaxpr(jaxpr)
    if j is None:
        return
    for eqn in j.eqns:
        yield EqnSite(eqn, multiplier, path)
        for sub, mult in sub_jaxprs(eqn):
            yield from iter_sites(sub, multiplier * mult,
                                  path + (eqn.primitive.name,))


def walk_jaxpr(jaxpr, visit: Callable[[EqnSite], None]) -> None:
    """Call ``visit(site)`` for every equation, including sub-jaxprs."""
    for site in iter_sites(jaxpr):
        visit(site)


def jaxpr_has_primitive(jaxpr, names: Iterable[str]) -> bool:
    """True when any equation (at any depth) uses one of ``names``."""
    names = tuple(names)
    return any(s.primitive in names for s in iter_sites(jaxpr))


def count_primitives(jaxpr, names: Iterable[str] = (),
                     weighted: bool = False) -> Dict[str, int]:
    """Occurrence count per primitive name; restricted to ``names`` when
    given.  ``weighted=True`` multiplies each occurrence by its static
    execution count (scan lengths)."""
    names = tuple(names)
    counts: Dict[str, int] = {}
    for s in iter_sites(jaxpr):
        if names and s.primitive not in names:
            continue
        counts[s.primitive] = counts.get(s.primitive, 0) \
            + (s.multiplier if weighted else 1)
    return counts


def iter_avals(jaxpr) -> Iterator[Any]:
    """Every abstract value a program touches: top-level in/out vars,
    constvars, and each equation's outputs at every depth."""
    j = _as_jaxpr(jaxpr)
    if j is None:
        return
    for v in list(j.invars) + list(j.constvars) + list(j.outvars):
        aval = getattr(v, "aval", None)
        if aval is not None:
            yield aval
    for site in iter_sites(j):
        for v in site.eqn.outvars:
            aval = getattr(v, "aval", None)
            if aval is not None:
                yield aval
