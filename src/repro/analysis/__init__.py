"""Graph-invariant linter: structured static analysis of traced jaxprs
and compiled HLO for the MoE stack.

The hazards that sink this system are graph-SHAPE bugs, not value bugs:
a pipeline's collectives folding into one scan-body equation (PR 5), f32
cotangents leaking into bf16 dots through ``ragged_dot``'s transpose
(PR 4), a serving path re-tracing per call (PR 7).  Outputs stay
numerically right while the emitted program quietly loses the property
the PR shipped.  This package checks the *program*:

* ``graph.JaxprGraph`` — a structured equation walker (recurses into
  scan/while/cond/pjit/shard_map/custom_vjp sub-jaxprs with loop-context
  tracking; no string matching),
* ``hlo.HloGraph`` — the compiled-module view, reusing
  ``launch/hlo_analysis.py``'s parser and loop-multiplier call graph,
* ``rules`` — a registry of ``Rule(name, level, check(Graph) ->
  [Finding])`` encoding every graph invariant the repo has shipped,
* ``lint`` — the config-matrix CLI:
  ``python -m repro.analysis.lint [--config NAME] [--json out.json]``
  traces sort/grouped × {1-rank, EP4, TP, EP×TP} × flat/hier ×
  overlap P ∈ {1,2,4}, writes a ``LINT_moe.json`` report, and exits
  nonzero on error-level findings.

Library entry points::

    from repro import analysis

    g = analysis.trace_graph(fn, *args, context={"cfg": cfg,
                                                 "model_size": 4, ...})
    findings = analysis.lint_jaxpr(g)            # all jaxpr rules
    findings = analysis.run_rule("dtype-leak", g)  # one rule
    findings = analysis.lint_hlo(compiled_text, context={...})
    findings = analysis.lint_probe(donated=train_state)

Adding a rule — the "new graph invariant ⇒ new rule + known-bad test"
convention (ROADMAP process note)::

    # 1. encode the invariant over the structured walker
    from repro.analysis.rules import register

    @register("fp8-payload", "error", ("jaxpr",))
    def _fp8_payload(graph):
        '''Quantized exchange payloads must cross the mesh in f8, not
        re-widened bf16.'''
        return [(site.describe(), "exchange payload widened before a2a")
                for site in graph.find("all_to_all")
                if any(str(d) == "bfloat16" for d in site.in_dtypes)]

    # 2. ship a KNOWN-BAD graph that makes it fire
    #    (tests/test_analysis.py: trace a deliberately-widened exchange,
    #    assert the finding), plus keep the clean matrix green.

Findings carry ``(rule, level, location, message, config)``; ``location``
is the structural path (``shard_map/scan/all_to_all``), so a finding
names WHERE in the program the invariant broke, not a substring offset.
"""
from __future__ import annotations

from typing import Any, Dict, Iterable, List, Optional

from repro.analysis.graph import (RAGGED_DOT_PRIMITIVES, EqnSite, JaxprGraph,
                                  ProbeGraph, trace_graph)
from repro.analysis.hlo import HloGraph, HloOpSite
from repro.analysis.rules import (COLLECTIVE_PRIMITIVES, DOT_PRIMITIVES,
                                  LEVELS, REGISTRY, Finding, Rule,
                                  lint_graph, register, rules_for, run_rule)

__all__ = [
    "COLLECTIVE_PRIMITIVES", "DOT_PRIMITIVES", "EqnSite", "Finding",
    "HloGraph", "HloOpSite", "JaxprGraph", "LEVELS", "ProbeGraph",
    "RAGGED_DOT_PRIMITIVES", "REGISTRY", "Rule", "lint_graph", "lint_hlo",
    "lint_jaxpr", "lint_probe", "register", "rules_for", "run_rule", "trace_graph",
]


def lint_jaxpr(graph_or_jaxpr, *, context: Optional[Dict[str, Any]] = None,
               rules: Optional[Iterable[str]] = None) -> List[Finding]:
    """Run the registered jaxpr rules.  Accepts a ``JaxprGraph`` or a
    raw (closed) jaxpr (wrapped with ``context``)."""
    g = (graph_or_jaxpr if isinstance(graph_or_jaxpr, JaxprGraph)
         else JaxprGraph(graph_or_jaxpr, context=context))
    return lint_graph(g, rules)


def lint_hlo(text_or_graph, *, context: Optional[Dict[str, Any]] = None,
             rules: Optional[Iterable[str]] = None) -> List[Finding]:
    """Run the registered HLO rules over compiled-module text (or an
    already-parsed ``HloGraph``)."""
    g = (text_or_graph if isinstance(text_or_graph, HloGraph)
         else HloGraph(text_or_graph, context=context))
    return lint_graph(g, rules)


def lint_probe(rules: Optional[Iterable[str]] = None,
               **context) -> List[Finding]:
    """Run the probe rules over runtime evidence, e.g.
    ``lint_probe(donated=state)`` or
    ``lint_probe(trace_counts=engine.trace_counts)``."""
    return lint_graph(ProbeGraph(context), rules)
