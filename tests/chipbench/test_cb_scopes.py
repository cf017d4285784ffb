"""The program's layer scopes and the readers of the per-layer metrics
built on them: the compiled train step names every layer in its ops'
metadata, forward and backward, with no op under two scopes; the readers
split busy time into the scopes and the unscoped rest, and read ``None``
from a program without the scopes."""
import dataclasses
from types import SimpleNamespace

import pytest

from cb_support import ROOT
from chipbench import hlo, scopes, spec
from chipbench.tracereduce import Op, Reduced, Trace

MS_METRICS = {"embed_ms": "embed", "attn_block_ms": "attention",
              "moe_gate_ms": "moe_gate", "moe_layout_ms": "moe_layout",
              "moe_expert_ms": "moe_experts", "head_loss_ms": "head_loss",
              "optimizer_ms": "optimizer"}


def _scopes_of(meta):
    return [s for s in scopes.SCOPES
            if any(n in meta for n in scopes.needles(s))]


@pytest.fixture(scope="module", params=[("sort", (1, 1)),
                                        ("grouped", (1, 4))])
def compiled_names(request):
    """op_name of every instruction of the tiny step's compiled HLO: the
    sort path on one device, the grouped path over four expert-parallel
    ones (so the exchange is in it)."""
    from repro import configs
    from repro.core.config import TrainConfig
    from repro.launch import mesh as mesh_lib
    from repro.launch import train

    dispatch, mesh_shape = request.param
    cfg = configs.smoke_config("hetumoe-paper-16e")
    cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, dispatch=dispatch))
    mesh = mesh_lib.make_smoke_mesh(mesh_shape)
    tr = train.build(cfg, TrainConfig(), mesh, batch=4, seq=64)
    text = tr.step.lower(tr.state, tr.batch(0), tr.rng).compile().as_text()
    return dispatch, hlo.op_names(text)


def test_every_scope_names_forward_and_backward_ops(compiled_names):
    dispatch, names = compiled_names
    seen = {(s, "transpose(" in m) for m in names.values()
            for s in _scopes_of(m)}
    want = set(scopes.SCOPES) - ({"moe_exchange"} if dispatch == "sort"
                                 else set())
    for s in want:
        assert (s, False) in seen, f"no forward op under {s}"
        if s != "optimizer":            # the update is not differentiated
            assert (s, True) in seen, f"no backward op under {s}"


def test_no_op_sits_under_two_scopes(compiled_names):
    _, names = compiled_names
    two = {m for m in names.values() if len(_scopes_of(m)) > 1}
    assert not two, sorted(two)[:5]


# -- the readers, on a synthetic trace ------------------------------------

SCAN = "jit(train_step)/jvp()/while/body/closed_call/"
BWD = "jit(train_step)/transpose(jvp())/while/body/closed_call/"
META = {
    "fusion.1": "jit(train_step)/jvp(embed)/gather",
    "fusion.2": SCAN + "attention/dot_general",
    "fusion.3": BWD + "attention/pallas_vmem/pallas_call",
    "fusion.4": SCAN + "moe_gate/reduce_max",
    "fusion.5": SCAN + "moe_layout/sort",
    "fusion.6": BWD + "moe_experts/etd,edf->etf/dot_general",
    "fusion.7": "jit(train_step)/transpose(jvp(head_loss))/dot_general",
    "fusion.8": "jit(train_step)/optimizer/jit(_where)/select_n",
    "fusion.9": "jit(train_step)/jvp()/while/body/dynamic_update_slice",
    # a longer name holding a scope's name is not that scope
    "fusion.10": "jit(train_step)/jit(embed_rows)/moe_gate_extra/add",
}


def _trace():
    ops = [Op(0, 10, "fusion.1"), Op(10, 20, "fusion.2"),
           Op(15, 30, "fusion.3"),                  # overlaps fusion.2
           Op(30, 35, "fusion.4"), Op(35, 45, "fusion.5"),
           Op(45, 60, "fusion.6"), Op(62, 80, "fusion.7"),
           Op(80, 95, "fusion.8"), Op(95, 100, "fusion.9"),
           Op(100, 104, "fusion.10"), Op(104, 106, "copy-start.3")]
    return Trace({"/device:TPU:0": ops}, [Op(0, 110, "fetch")])


def _read(name, names=META, steps=2):
    red = Reduced(_trace(), lo=0, hi=110, names=names)
    ctx = SimpleNamespace(reduced=red, steps=steps)
    return spec.metric_reader(name, ROOT)(ctx), red


def test_scope_readers_partition_busy_time():
    want = {"embed_ms": 10, "attn_block_ms": 20, "moe_gate_ms": 5,
            "moe_layout_ms": 10, "moe_expert_ms": 15, "head_loss_ms": 18,
            "optimizer_ms": 15}
    total = 0.0
    for name, ns in want.items():
        ms, red = _read(name)
        assert ms == pytest.approx(1e3 * ns / 1e9 / 2), name
        total += ms
    pct, red = _read("unscoped_pct")
    # fusion.9, fusion.10 and the copy: 11 of 104 busy ns
    assert red.busy_s == pytest.approx(104e-9)
    assert pct == pytest.approx(100 * 11 / 104)
    busy_ms_per_step = 1e3 * red.busy_s / 2
    assert total + pct / 100 * busy_ms_per_step == \
        pytest.approx(busy_ms_per_step)


def test_readers_read_none_without_the_scopes():
    parent = {"fusion.1": "jit(train_step)/jvp()/gather",
              "fusion.3": BWD + "pallas_vmem/pallas_call"}
    for name in [*MS_METRICS, "unscoped_pct"]:
        assert _read(name, names=parent)[0] is None, name
    # a scope the trace lacks reads None beside those it has
    only_embed = {"fusion.1": META["fusion.1"]}
    assert _read("moe_gate_ms", names=only_embed)[0] is None
    assert _read("embed_ms", names=only_embed)[0] is not None


def test_flash_reader_still_finds_the_kernel_under_attention():
    ms, _ = _read("flash_attn_ms")
    assert ms == pytest.approx(1e3 * 15e-9 / 2)


def test_the_metrics_are_in_the_benchmark():
    import json
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name in [*MS_METRICS, "unscoped_pct"]:
        m = entries[name]
        assert m["source"] == "device_trace"
        assert m["moves"] == "tokens_per_s_per_chip"
        assert m["workloads"] == ["paper16e-switch-sort-1chip",
                                  "paper16e-gshard-grouped-1chip"]
    assert set(MS_METRICS.values()) | {"moe_exchange"} == set(scopes.SCOPES)


# -- the grouped-matmul kernels' readers -----------------------------------

EXPERTS = "/while/body/closed_call/moe_experts/"
GROUPED = {
    "fusion.20": "jit(train_step)/jvp()" + EXPERTS
                 + "jit(_grouped_matmul_impl)/pallas_call",
    "fusion.21": "jit(train_step)/transpose(jvp())" + EXPERTS
                 + "jit(_grouped_matmul_impl)/pallas_call",
    "fusion.22": "jit(train_step)/transpose(jvp())" + EXPERTS
                 + "jit(_grouped_drhs_impl)/pallas_call",
    # the block maps beside the kernel are not the kernel
    "fusion.23": "jit(train_step)/transpose(jvp())" + EXPERTS
                 + "jit(_grouped_matmul_impl)/jit(searchsorted)/gather",
    "fusion.24": "jit(train_step)/jvp()/while/body/closed_call/moe_layout/"
                 "jit(_gather_rows_impl)/pallas_call",
}


def _grouped_ctx(names, steps=2):
    ops = [Op(0, 40, "fusion.20"), Op(40, 100, "fusion.21"),
           Op(100, 180, "fusion.22"), Op(180, 190, "fusion.23"),
           Op(190, 200, "fusion.24")]
    red = Reduced(Trace({"/device:TPU:0": ops}, [Op(0, 200, "fetch")]),
                  lo=0, hi=200, names=names)
    # the paper's model under the GShard gate, 16384 assignments a step
    paper = spec.load_cell("paper16e-switch-sort-1chip")
    cell = dataclasses.replace(paper, config=dict(
        paper.config, model=dict(paper.model, gate="gshard",
                                 experts_per_token=2)))
    return SimpleNamespace(reduced=red, steps=steps, cell=cell, chips=1,
                           counts=spec.module(cell.counts),
                           peaks=lambda: spec.peaks("TPU v5 lite"))


def test_grouped_ffn_readers():
    from chipbench import counts
    ctx = _grouped_ctx(GROUPED)
    ms = spec.metric_reader("grouped_ffn_ms", ROOT)(ctx)
    assert ms == pytest.approx(1e3 * 180e-9 / 2)
    flops, nbytes = counts.grouped_matmul_work(ctx.cell.model, 8, 1024, 1)
    least = max(flops / 197e12, nbytes / 819e9)
    pct = spec.metric_reader("grouped_ffn_roofline", ROOT)(ctx)
    assert pct == pytest.approx(100 * least / (ms / 1e3))
    assert least == pytest.approx(flops / 197e12)      # bound by operations


def test_grouped_ffn_readers_read_none_without_the_kernels():
    ctx = _grouped_ctx(META)
    for name in ("grouped_ffn_ms", "grouped_ffn_roofline"):
        assert spec.metric_reader(name, ROOT)(ctx) is None, name
