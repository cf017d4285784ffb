"""Structured graph representations for the lint rules.

The hazards this repo keeps hitting are *graph-shape* bugs — a collective
folded into a scan body, a recompute hiding in a backward sub-jaxpr, an
f32 operand sneaking into a bf16 dot — and substring-matching
``str(jax.make_jaxpr(...))`` cannot see structure: it miscounts when
primitive names nest (``all_to_all`` inside a transposed sub-jaxpr), and
it cannot tell a forward ``ragged_dot`` from one re-run by a VJP.

``JaxprGraph`` walks a (closed) jaxpr as a tree of equations, recursing
into every sub-jaxpr carried in ``eqn.params`` — ``scan``/``while``
bodies, ``cond`` branches, ``pjit``/``shard_map``/``custom_vjp``
call jaxprs, remat — and tags each equation site with

* ``path``       the enclosing primitive names, outermost first
                 (``("shard_map", "pjit", "scan")``),
* ``loop_depth`` how many *loop bodies* (``scan``/``while``) enclose it
                 (``cond`` branches and ``pjit`` calls do not count),
* ``trip``       the product of statically-known enclosing trip counts
                 (``scan``'s ``length``; 1 where unknown).

Rules consume sites through :meth:`JaxprGraph.sites` /
:meth:`JaxprGraph.find` / :meth:`JaxprGraph.count` and never look at the
string form.  ``ProbeGraph`` is the non-graph variant for rules over
runtime evidence (donated pytrees, ``engine.trace_counts``).
"""
from __future__ import annotations

from collections import Counter
from typing import (AbstractSet, Any, Dict, Iterator, List, NamedTuple,
                    Optional, Tuple, Union)

import jax

try:  # public home since jax 0.4.35
    from jax.extend.core import ClosedJaxpr, Jaxpr, JaxprEqn
except ImportError:  # pragma: no cover - older jax
    from jax.core import ClosedJaxpr, Jaxpr, JaxprEqn  # type: ignore

# ``lax.ragged_dot`` traces as ``ragged_dot_general`` on current jax and as
# ``ragged_dot`` on older releases; rules and tests match the family.
RAGGED_DOT_PRIMITIVES = frozenset({"ragged_dot", "ragged_dot_general"})

# params whose sub-jaxpr is a LOOP BODY: entering it means the enclosed
# eqns execute once per iteration (scan also carries a static `length`).
_LOOP_PARAMS = {
    "scan": ("jaxpr",),
    "while": ("body_jaxpr", "cond_jaxpr"),
}


class EqnSite(NamedTuple):
    """One equation plus its structural context."""
    eqn: JaxprEqn
    path: Tuple[str, ...]        # enclosing primitive names, outermost first
    loop_depth: int              # enclosing scan/while bodies
    trip: int                    # product of known enclosing trip counts

    @property
    def primitive(self) -> str:
        return self.eqn.primitive.name

    @property
    def in_avals(self) -> Tuple[Any, ...]:
        return tuple(v.aval for v in self.eqn.invars if hasattr(v, "aval"))

    @property
    def out_avals(self) -> Tuple[Any, ...]:
        return tuple(v.aval for v in self.eqn.outvars if hasattr(v, "aval"))

    @property
    def out_shapes(self) -> Tuple[Tuple[int, ...], ...]:
        return tuple(tuple(a.shape) for a in self.out_avals
                     if hasattr(a, "shape"))

    @property
    def in_dtypes(self) -> Tuple[Any, ...]:
        return tuple(a.dtype for a in self.in_avals if hasattr(a, "dtype"))

    def describe(self) -> str:
        """Human-readable location: ``shard_map/scan/all_to_all``."""
        return "/".join(self.path + (self.primitive,))


def _sub_jaxprs(eqn: JaxprEqn) -> Iterator[Tuple[Jaxpr, bool, int]]:
    """Yield ``(jaxpr, is_loop_body, trip)`` for every sub-jaxpr carried
    in the equation's params (tuples/lists of jaxprs included — ``cond``
    branches)."""
    loop_keys = _LOOP_PARAMS.get(eqn.primitive.name, ())
    trip = int(eqn.params.get("length", 1) or 1) \
        if eqn.primitive.name == "scan" else 1
    for key, val in eqn.params.items():
        vals = val if isinstance(val, (tuple, list)) else (val,)
        for v in vals:
            if isinstance(v, ClosedJaxpr):
                v = v.jaxpr
            if isinstance(v, Jaxpr):
                yield v, key in loop_keys, (trip if key in loop_keys else 1)


def _walk(jaxpr: Jaxpr, path: Tuple[str, ...], loop_depth: int,
          trip: int) -> Iterator[EqnSite]:
    for eqn in jaxpr.eqns:
        yield EqnSite(eqn, path, loop_depth, trip)
        sub_path = path + (eqn.primitive.name,)
        for sub, is_loop, sub_trip in _sub_jaxprs(eqn):
            yield from _walk(sub, sub_path,
                             loop_depth + (1 if is_loop else 0),
                             trip * sub_trip)


class JaxprGraph:
    """A traced program plus the lint context it was traced under.

    ``context`` keys the shipped rules understand (all optional — a rule
    that misses its context simply does not apply):

      cfg                the ``MoEConfig`` the graph was traced with
      model_size         expert-parallel degree (mesh ``model`` axis)
      tokens_per_shard   static per-shard token count fed to the layer
      d_model            model width (payload-shape checks)
      direction          "fwd" | "grad"
      label              location prefix for findings (e.g. config name)
      expect_no_ragged   force the no-recompute-backward rule on
    """
    kind = "jaxpr"

    def __init__(self, closed: ClosedJaxpr,
                 context: Optional[Dict[str, Any]] = None):
        if not isinstance(closed, (ClosedJaxpr, Jaxpr)):
            raise TypeError(
                f"JaxprGraph wants a (Closed)Jaxpr — trace first with "
                f"jax.make_jaxpr or use analysis.trace_graph; got "
                f"{type(closed).__name__}")
        self.closed = closed
        self.jaxpr = closed.jaxpr if isinstance(closed, ClosedJaxpr) else closed
        self.context: Dict[str, Any] = dict(context or {})
        self._sites: Optional[List[EqnSite]] = None

    def sites(self) -> List[EqnSite]:
        if self._sites is None:
            self._sites = list(_walk(self.jaxpr, (), 0, 1))
        return self._sites

    def find(self, primitive: Union[str, AbstractSet[str]]) -> List[EqnSite]:
        """Sites of one primitive, or of any in a set of names (e.g.
        :data:`RAGGED_DOT_PRIMITIVES`)."""
        names = {primitive} if isinstance(primitive, str) else primitive
        return [s for s in self.sites() if s.primitive in names]

    def count(self, primitive: Union[str, AbstractSet[str]]) -> int:
        return len(self.find(primitive))

    def primitives(self) -> Counter:
        return Counter(s.primitive for s in self.sites())

    @property
    def label(self) -> str:
        return str(self.context.get("label", "<jaxpr>"))


class ProbeGraph:
    """Runtime-evidence 'graph' for the probe rules (donation aliasing,
    serving retrace budget).  Carries only ``context``."""
    kind = "probe"

    def __init__(self, context: Optional[Dict[str, Any]] = None):
        self.context: Dict[str, Any] = dict(context or {})

    @property
    def label(self) -> str:
        return str(self.context.get("label", "<probe>"))


def trace_graph(fn, *args, context: Optional[Dict[str, Any]] = None,
                **make_jaxpr_kwargs) -> JaxprGraph:
    """``jax.make_jaxpr`` + wrap: the one-liner the tests and the lint
    CLI use instead of ``str(jax.make_jaxpr(...))`` grepping."""
    closed = jax.make_jaxpr(fn, **make_jaxpr_kwargs)(*args)
    return JaxprGraph(closed, context=context)
