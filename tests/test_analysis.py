"""The graph-invariant linter (``repro.analysis``).

Coverage contract (the ROADMAP "new graph invariant ⇒ new rule +
known-bad test" convention, applied to the shipped rules themselves):
every registered rule has a KNOWN-BAD case here that makes it fire, and
the full dispatch config matrix runs CLEAN at error level in-process
(the negative control proving the rules stay quiet on healthy graphs).
Also covers the structured walker (loop depth, sub-jaxpr recursion,
structural paths), the HLO-side graph incl. the f8 dtype table, and the
``python -m repro.analysis.lint`` CLI (bad-config cells become findings
+ exit 1, never tracebacks; the full-matrix subprocess run is
slow-marked and diffs against the committed ``LINT_moe.json``).
"""
import json
import os
import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from repro import analysis
from repro.analysis import lint as lint_cli
from repro.core import moe
from repro.core.config import MoEConfig
from repro.launch import hlo_analysis as H

RNG = jax.random.PRNGKey(3)
REPO = pathlib.Path(__file__).resolve().parents[1]


# ---------------------------------------------------------------------------
# the structured walker
# ---------------------------------------------------------------------------

def test_walker_recurses_into_loop_bodies_with_depth_and_trip():
    def f(x):
        def body(c, t):
            return c + jnp.dot(t, t), ()
        c, _ = jax.lax.scan(body, jnp.zeros(()), x)
        return c

    g = analysis.trace_graph(f, jnp.ones((5, 3)))
    dots = g.find("dot_general")
    assert len(dots) == 1
    site = dots[0]
    assert site.loop_depth == 1
    assert site.trip == 5                      # scan length propagated
    assert site.describe().endswith("scan/dot_general")


def test_walker_recurses_into_cond_branches_without_loop_depth():
    def f(x, flag):
        return jax.lax.cond(flag, lambda v: jnp.dot(v, v), lambda v: v * 2.0,
                            x)

    g = analysis.trace_graph(f, jnp.ones((3, 3)), True)
    assert g.count("dot_general") == 1
    assert all(s.loop_depth == 0 for s in g.find("dot_general"))


def test_trace_graph_context_and_primitives_counter():
    g = analysis.trace_graph(lambda a, b: jnp.dot(a, b) + 1.0,
                             jnp.ones((2, 2)), jnp.ones((2, 2)),
                             context={"label": "unit"})
    assert g.label == "unit"
    assert g.primitives()["dot_general"] == 1


# ---------------------------------------------------------------------------
# per-rule known-bad graphs (each rule must FIRE somewhere)
# ---------------------------------------------------------------------------

def test_known_bad_collective_in_loop_jaxpr(mesh_ep4):
    """The PR 5 anti-pattern: pipelining via fori_loop/scan folds every
    exchange into ONE loop-body collective."""
    from jax.sharding import PartitionSpec as P

    def layer(x):          # x (4, d) local block, scans 4 "chunks"
        def body(c, t):
            return c + jax.lax.psum(t, "model"), ()
        c, _ = jax.lax.scan(body, jnp.zeros(x.shape[-1]), x)
        return c

    fn = jax.shard_map(layer, mesh=mesh_ep4, in_specs=P(None, None),
                       out_specs=P(None), check_vma=False)
    g = analysis.trace_graph(fn, jnp.ones((4, 8)))
    findings = analysis.run_rule("collective-in-loop", g)
    assert len(findings) == 1
    f = findings[0]
    assert f.level == "error"
    assert "psum" in f.message and "loop body" in f.message
    assert "scan" in f.location                # structural path, not offset
    # the same graph is clean when the loop is explicitly allowed
    g.context["allow_loop_collectives"] = True
    assert analysis.run_rule("collective-in-loop", g) == []


def test_known_bad_overlap_chunk_count(mesh_ep4):
    """An unchunked (P=1) pipeline linted against a P=4 contract must
    miss on both the equation count and the payload windows."""
    mk = lambda P: MoEConfig(num_experts=8, dispatch="grouped", gate="topk",
                             top_k=2, capacity_factor=8.0, overlap_chunks=P)
    cfg1 = mk(1)
    p = moe.init_moe_params(RNG, cfg1, 32, 64, 8, act="swiglu",
                            dtype=jnp.float32)
    x = jax.random.normal(RNG, (4, 16, 32))
    g = analysis.trace_graph(
        lambda p_, v: moe.sharded_moe_apply(mesh_ep4, cfg1, p_, v,
                                            num_experts=8, act="swiglu"),
        p, x, context={"cfg": mk(4), "model_size": 4, "tokens_per_shard": 16,
                       "d_model": 32, "direction": "fwd"})
    findings = analysis.run_rule("overlap-chunk-count", g)
    assert len(findings) == 2, findings
    count_f, payload_f = findings
    assert "12 all_to_all equations, traced 3" in count_f.message
    assert "(4, 8, 32)" in payload_f.message   # (M, B/P, d) window


def test_known_bad_tuned_plan_consistency(mesh_ep4):
    """A flat/P=1 graph linted against an ``"auto"``-knob contract: the
    tuner resolves hierarchical (stages=2) for this tiny cell, so the
    traced graph misses on both the equation count (3 vs 5) and the
    payload-window count — "auto" silently changed a traced graph shape,
    the exact drift the rule exists to catch."""
    import dataclasses
    concrete = MoEConfig(num_experts=8, dispatch="grouped", gate="topk",
                         top_k=2, capacity_factor=8.0, a2a="flat",
                         overlap_chunks=1)
    auto = dataclasses.replace(concrete, a2a="auto", overlap_chunks="auto",
                               grouped_block_m="auto",
                               grouped_ep_bound_factor="auto")
    p = moe.init_moe_params(RNG, concrete, 32, 64, 8, act="swiglu",
                            dtype=jnp.float32)
    x = jax.random.normal(RNG, (4, 16, 32))
    ctx = {"cfg": auto, "model_size": 4, "tokens_per_shard": 16,
           "d_model": 32, "direction": "fwd", "dtype": jnp.float32}
    g = analysis.trace_graph(
        lambda p_, v: moe.sharded_moe_apply(mesh_ep4, concrete, p_, v,
                                            num_experts=8, act="swiglu"),
        p, x, context=ctx)
    findings = analysis.run_rule("tuned-plan-consistency", g)
    assert len(findings) == 2, findings
    count_f, payload_f = findings
    assert "a2a='hierarchical'" in count_f.message
    assert "expects 5 all_to_all equations, traced 3" in count_f.message
    assert payload_f.level == "error"
    # positive control: the graph traced from the SAME auto config is
    # consistent with the plan the rule resolves
    g_auto = analysis.trace_graph(
        lambda p_, v: moe.sharded_moe_apply(mesh_ep4, auto, p_, v,
                                            num_experts=8, act="swiglu"),
        p, x, context=ctx)
    assert analysis.run_rule("tuned-plan-consistency", g_auto) == []
    # concrete-config cells stay owned by overlap-chunk-count
    g.context["cfg"] = concrete
    assert analysis.run_rule("tuned-plan-consistency", g) == []


def test_known_bad_payload_dtype(mesh_ep4):
    """PR 10, both failure directions.  A full-width (f32) exchange
    linted against an ``payload_dtype="int8"`` contract means the
    quantize/dequantize pair was dropped; an int8 exchange linted
    against a payload-unset contract means low-precision wire dtypes
    are leaking where the config promises the compute dtype."""
    import dataclasses
    full = MoEConfig(num_experts=8, dispatch="grouped", gate="topk",
                     top_k=2, capacity_factor=8.0)
    quant = dataclasses.replace(full, payload_dtype="int8")
    p = moe.init_moe_params(RNG, full, 32, 64, 8, act="swiglu",
                            dtype=jnp.float32)
    x = jax.random.normal(RNG, (4, 16, 32))
    ctx = lambda cfg: {"cfg": cfg, "model_size": 4, "tokens_per_shard": 16,
                       "d_model": 32, "direction": "fwd",
                       "dtype": jnp.float32}
    trace = lambda cfg, c: analysis.trace_graph(
        lambda p_, v: moe.sharded_moe_apply(mesh_ep4, cfg, p_, v,
                                            num_experts=8, act="swiglu"),
        p, x, context=ctx(c))

    # quantization promised but never applied: every payload window is
    # still full-width on the wire
    findings = analysis.run_rule("payload-dtype", trace(full, quant))
    assert findings and all(f.level == "error" for f in findings)
    assert all("quantize/dequantize pair" in f.message for f in findings)
    assert any("int8" in f.message and "float32" in f.message
               for f in findings)
    # the reverse leak: int8 on a wire the config says is full-width
    findings = analysis.run_rule("payload-dtype", trace(quant, full))
    assert findings and all("int8" in f.message for f in findings)
    # positive controls: graph and contract agree, both ways
    assert analysis.run_rule("payload-dtype", trace(quant, quant)) == []
    assert analysis.run_rule("payload-dtype", trace(full, full)) == []


def test_known_bad_no_recompute_backward():
    """Differentiating raw ``lax.ragged_dot`` re-runs it in the VJP —
    the exact recompute the custom_vjp kernels exist to avoid."""
    lhs = jax.random.normal(RNG, (32, 8))
    rhs = jax.random.normal(RNG, (4, 8, 8))
    sizes = jnp.array([10, 6, 0, 16], jnp.int32)
    cfg = MoEConfig(num_experts=4, dispatch="grouped", gate="topk", top_k=2,
                    capacity_factor=8.0, use_pallas_gate=True)
    g = analysis.trace_graph(
        jax.grad(lambda l: jnp.sum(jax.lax.ragged_dot(l, rhs, sizes) ** 2)),
        lhs, context={"cfg": cfg, "direction": "grad"})
    findings = analysis.run_rule("no-recompute-backward", g)
    assert findings and all(f.level == "error" for f in findings)
    assert any("ragged_dot" in f.location for f in findings)
    # the gate: a forward graph under the same config is out of scope
    g.context["direction"] = "fwd"
    assert analysis.run_rule("no-recompute-backward", g) == []


def test_known_bad_dtype_leak():
    """An f32 operand against a bf16 one traces without complaint — the
    rule is the only thing that notices the missing cast."""
    a32 = jnp.ones((4, 8), jnp.float32)
    b16 = jnp.ones((8, 4), jnp.bfloat16)
    dot = lambda a, b: jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())))
    bad = analysis.trace_graph(dot, a32, b16)
    findings = analysis.run_rule("dtype-leak", bad)
    assert len(findings) == 1
    assert "bfloat16" in findings[0].message
    assert "float32" in findings[0].message
    # f32 ACCUMULATION via preferred_element_type is fine by design
    ok = analysis.trace_graph(
        lambda a, b: jax.lax.dot_general(
            a, b, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32),
        b16.T, b16)
    assert analysis.run_rule("dtype-leak", ok) == []
    # integer group_sizes next to float operands are exempt
    sizes = jnp.array([2, 2], jnp.int32)
    ragged = analysis.trace_graph(
        lambda l, r: jax.lax.ragged_dot(l, r, sizes),
        jnp.ones((4, 8), jnp.bfloat16), jnp.ones((2, 8, 4), jnp.bfloat16))
    assert analysis.run_rule("dtype-leak", ragged) == []


def test_known_bad_donation_alias():
    z = jnp.zeros((), jnp.int32)
    findings = analysis.lint_probe(donated={"a": z, "b": z, "c": z + 1})
    assert len(findings) == 1
    f = findings[0]
    assert f.rule == "donation-alias" and f.level == "error"
    assert "'a'" in f.location and "'b'" in f.location
    # distinct buffers: clean
    ok = {"a": jnp.zeros((), jnp.int32), "b": jnp.zeros((), jnp.int32)}
    assert analysis.lint_probe(donated=ok) == []


def test_real_train_state_donation_is_alias_free():
    """The probe the CLI runs: a freshly initialized TrainState (the
    pytree ``make_train_step`` donates) has no shared buffers."""
    from repro import configs
    from repro.core.config import TrainConfig
    from repro.training.train_step import init_train_state

    cfg = configs.smoke_config("dbrx-132b").replace(dtype="float32")
    state = init_train_state(jax.random.PRNGKey(0), cfg, TrainConfig())
    assert analysis.lint_probe(donated=state) == []


def test_known_bad_retrace_budget():
    counts = {("decode", "dbrx", 1, 32): 3, ("prefill", "dbrx", 1, 32): 1}
    findings = analysis.lint_probe(trace_counts=counts)
    assert len(findings) == 1
    assert findings[0].rule == "retrace-budget"
    assert "3x" in findings[0].message
    assert analysis.lint_probe(trace_counts=counts, budget=3) == []


def test_known_bad_config_invalid():
    findings = analysis.lint_probe(config_error="P does not divide B",
                                   label="grouped/ep4/flat/P5")
    assert len(findings) == 1
    f = findings[0]
    assert f.rule == "config-invalid" and f.level == "error"
    assert f.location == "grouped/ep4/flat/P5"
    assert "does not divide" in f.message


# ---------------------------------------------------------------------------
# HLO-side graph + the f8 dtype table (launch/hlo_analysis.py)
# ---------------------------------------------------------------------------

_F8_HLO = """\
HloModule synth

%body (p: (s32[], f8e4m3fn[4,16])) -> (s32[], f8e4m3fn[4,16]) {
  %p = (s32[], f8e4m3fn[4,16]) parameter(0)
  %it = s32[] get-tuple-element((s32[], f8e4m3fn[4,16]) %p), index=0
  %one = s32[] constant(1)
  %next = s32[] add(s32[] %it, s32[] %one)
  %buf = f8e4m3fn[4,16] get-tuple-element((s32[], f8e4m3fn[4,16]) %p), index=1
  %xchg = f8e4m3fn[4,16] all-to-all(f8e4m3fn[4,16] %buf), replica_groups=[1,4]
  ROOT %out = (s32[], f8e4m3fn[4,16]) tuple(s32[] %next, f8e4m3fn[4,16] %xchg)
}

%cond (p: (s32[], f8e4m3fn[4,16])) -> pred[] {
  %p = (s32[], f8e4m3fn[4,16]) parameter(0)
  %it = s32[] get-tuple-element((s32[], f8e4m3fn[4,16]) %p), index=0
  %n = s32[] constant(3)
  ROOT %lt = pred[] compare(s32[] %it, s32[] %n), direction=LT
}

ENTRY %main (arg: f8e4m3fn[4,16], wide: f8e4m3fnuz[8]) -> f8e4m3fn[4,16] {
  %arg = f8e4m3fn[4,16] parameter(0)
  %wide = f8e4m3fnuz[8] parameter(1)
  %zero = s32[] constant(0)
  %init = (s32[], f8e4m3fn[4,16]) tuple(s32[] %zero, f8e4m3fn[4,16] %arg)
  %w = (s32[], f8e4m3fn[4,16]) while((s32[], f8e4m3fn[4,16]) %init), \
condition=%cond, body=%body
  ROOT %res = f8e4m3fn[4,16] get-tuple-element((s32[], f8e4m3fn[4,16]) %w), \
index=1
}
"""


def test_hlo_parser_sizes_f8_ops():
    """Satellite: the roofline's dtype table covers the f8 families, so
    a quantized exchange buffer keeps its byte counts."""
    comps, shapes = H.parse_module(_F8_HLO)
    a2a = [op for op in comps["body"] if op.kind == "all-to-all"]
    assert len(a2a) == 1
    assert a2a[0].result_bytes == 4 * 16 * 1          # 1 byte/elem, not 0/4
    assert a2a[0].result_dims == [("f8e4m3fn", [4, 16])]
    # longest-first alternation: f8e4m3fnuz must not parse as
    # f8e4m3fn + stray text (8 bytes, one dim of 8)
    assert shapes["wide"] == (8, [("f8e4m3fnuz", [8])])
    for dt in ("f8e4m3fn", "f8e5m2", "f8e4m3b11fnuz"):
        assert H._DTYPE_BYTES[dt] == 1


def test_known_bad_collective_in_loop_hlo():
    """HLO side of the rule: the while-wrapped all-to-all above executes
    every iteration (×3 trip) — exactly what the jaxpr-side rule cannot
    see once XLA re-schedules."""
    g = analysis.HloGraph(_F8_HLO, context={"label": "synth"})
    assert g.entry == "main"
    assert g.in_loop["body"] and g.in_loop["cond"]
    assert g.mult["body"] == 3.0                      # trip from %cond
    findings = analysis.lint_hlo(g)
    assert [f.rule for f in findings] == ["collective-in-loop"]
    assert "while body" in findings[0].message
    assert findings[0].location == "body/all-to-all"


def test_hlo_graph_clean_when_collective_at_top_level():
    txt = """\
HloModule ok

ENTRY %main (arg: bf16[4,16]) -> bf16[4,16] {
  %arg = bf16[4,16] parameter(0)
  ROOT %xchg = bf16[4,16] all-to-all(bf16[4,16] %arg), replica_groups=[1,4]
}
"""
    g = analysis.HloGraph(txt)
    assert g.count("all-to-all") == 1
    assert analysis.lint_hlo(g) == []
    with pytest.raises(ValueError, match="no computations"):
        analysis.HloGraph("not hlo at all")


# ---------------------------------------------------------------------------
# the clean matrix (negative control: every rule quiet on healthy graphs)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("cell", lint_cli.matrix_cells())
def test_matrix_cell_lints_clean(cell):
    assert lint_cli.lint_cell(cell) == []


def test_matrix_covers_the_contracted_shapes():
    cells = lint_cli.matrix_cells()
    assert len(cells) == len(set(cells))
    for want in ("sort/r1/flat/P1", "grouped/ep4/hier/P4",
                 "grouped/ep2tp2/flat/P2", "grouped/tp2/flat/P4",
                 "decode/ep4/grouped/P1",
                 # PR 9: every mesh gets an all-knobs-"auto" cell, plus
                 # the auto decode cell (step-BUILD-time resolution)
                 "grouped/r1/auto/Pauto", "grouped/ep4/auto/Pauto",
                 "grouped/tp2/auto/Pauto", "grouped/ep2tp2/auto/Pauto",
                 "decode/ep4/grouped/Pauto",
                 # PR 10: quantized-wire cells (int8 + one fp8) across
                 # flat/hier, P=1/2, EP and EP×TP, plus a decode cell
                 "grouped/ep4/flat/P1/int8", "grouped/ep4/flat/P2/int8",
                 "grouped/ep4/hier/P1/float8_e4m3fn",
                 "grouped/ep2tp2/flat/P2/int8",
                 "decode/ep4/grouped/P1/int8"):
        assert want in cells
    # hier cells only exist where a model axis exists to factorize
    assert not any("/r1/hier/" in c or "/tp2/hier/" in c for c in cells)


def test_lint_cell_rejects_unknown_vocabulary():
    with pytest.raises(ValueError, match="bad lint cell"):
        lint_cli.parse_cell("grouped/ep4/flat")
    with pytest.raises(ValueError, match="bad lint cell"):
        lint_cli.parse_cell("groped/ep4/flat/P2")
    with pytest.raises(ValueError, match="bad lint cell"):
        lint_cli.parse_cell("grouped/ep4/flat/Px")


def test_bad_overlap_bound_is_a_finding_not_a_traceback():
    """Satellite: the validator error paths surface as findings through
    the same lint_cell the CLI drives."""
    for cell in ("grouped/ep4/flat/P5", "decode/ep4/grouped/P3"):
        findings = lint_cli.lint_cell(cell)
        assert [f.rule for f in findings] == ["config-invalid"], cell
        assert "overlap_chunks" in findings[0].message


# ---------------------------------------------------------------------------
# the CLI (subprocess; report schema diffable like BENCH_moe.json)
# ---------------------------------------------------------------------------

def _run_cli(*extra):
    env = {**os.environ, "PYTHONPATH": "src",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=8"}
    return subprocess.run(
        [sys.executable, "-m", "repro.analysis.lint", *extra],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=900)


def test_cli_bad_config_exits_nonzero_with_report(tmp_path):
    out = tmp_path / "lint.json"
    r = _run_cli("--config", "decode/ep4/grouped/P3", "--json", str(out))
    assert r.returncode == 1, r.stdout + r.stderr
    assert "Traceback" not in r.stderr
    assert "config-invalid" in r.stdout
    report = json.loads(out.read_text())
    assert report["schema"] == lint_cli.SCHEMA
    assert report["summary"]["error"] == 1
    [finding] = report["findings"]
    assert finding["rule"] == "config-invalid"
    assert finding["config"] == "decode/ep4/grouped/P3"


def test_cli_unknown_cell_is_an_argparse_error(tmp_path):
    r = _run_cli("--config", "grouped/nope/flat/P2",
                 "--json", str(tmp_path / "l.json"))
    assert r.returncode == 2, r.stdout + r.stderr
    assert "bad lint cell" in r.stderr


@pytest.mark.slow
def test_cli_full_matrix_clean_and_matches_committed_report(tmp_path):
    """The acceptance run: full matrix + HLO pass + probes, exit 0, and
    the scratch report agrees with the committed LINT_moe.json on
    schema, rules, matrix, and finding count."""
    out = tmp_path / "lint.json"
    r = _run_cli("--json", str(out))
    assert r.returncode == 0, r.stdout + r.stderr
    fresh = json.loads(out.read_text())
    committed = json.loads((REPO / "LINT_moe.json").read_text())
    assert fresh["schema"] == committed["schema"]
    assert fresh["matrix"] == committed["matrix"]
    assert sorted(fresh["rules"]) == sorted(committed["rules"])
    assert fresh["findings"] == committed["findings"] == []
    assert fresh["summary"]["error"] == 0
