"""The trace reduction on a small synthetic trace, and ``load`` on a
trace the profiler writes here."""
import pytest

from chipbench import tracereduce as tr
from chipbench.tracereduce import Op, Reduced, Trace

GMM = "jit(train_step)/shard_map/jit(_grouped_matmul_impl)/pallas_call"


def _trace():
    # device 0: two overlapping ops, an all-to-all with one op inside it,
    # and a kernel known only by the compiled HLO's op name
    dev0 = [Op(0, 10, "fusion.1"), Op(5, 20, "fusion.2"),
            Op(25, 40, "all-to-all.7"), Op(30, 35, "fusion.3"),
            Op(50, 60, "custom-call.4")]
    # device 1: one op covering its whole busy time, a collective alone
    dev1 = [Op(0, 30, "fusion.1"), Op(30, 50, "all_to_all.9")]
    spans = [Op(0, 2, "place"), Op(2, 22, "dispatch"), Op(22, 70, "fetch")]
    return Trace({"/device:TPU:0": dev0, "/device:TPU:1": dev1}, spans)


@pytest.fixture
def red():
    return Reduced(_trace(), names={"custom-call.4": GMM})


def test_busy_union_and_idle_share(red):
    assert tr.union([(5, 20), (0, 10), (25, 40), (30, 35)]) == \
        [(0, 20), (25, 40)]
    assert (red.lo, red.hi) == (0, 70)
    assert red.busy["/device:TPU:0"] == [(0, 20), (25, 40), (50, 60)]
    assert red.busy_s == pytest.approx((45 + 50) / 2 / 1e9)
    assert red.window_s == pytest.approx(70 / 1e9)
    assert red.idle_share == pytest.approx(1 - 47.5 / 70)


def test_kernel_time_by_name_and_op_metadata(red):
    assert red.op_seconds(["jit(_grouped_matmul_impl)/pallas_call"]) == \
        pytest.approx(10 / 2 / 1e9)
    assert red.op_seconds(["fusion.1"]) == pytest.approx((10 + 30) / 2 / 1e9)
    assert red.op_seconds(["no-such-kernel"]) == 0


def test_exposed_collective_time(red):
    # device 0: [25, 40) less [30, 35) = 10; device 1: 20, nothing overlaps
    assert red.exposed_collective_seconds(["all-to-all", "all_to_all"]) == \
        pytest.approx((10 + 20) / 2 / 1e9)
    assert tr.subtract([(0, 10), (20, 30)], [(2, 4), (8, 22)]) == \
        [(0, 2), (4, 8), (22, 30)]


def test_gaps_named_by_host_span(red):
    # device 0 idles in [20, 25) (3 ns under fetch, 2 under dispatch),
    # [40, 50) and [60, 70), all under fetch
    gaps = red.gaps(10)
    assert gaps[0][0] == "fetch" and gaps[0][1] == pytest.approx(10e-9)
    assert sorted(g for _, g in gaps) == pytest.approx([5e-9, 10e-9, 10e-9])
    assert {n for n, _ in gaps} == {"fetch"}


def test_top_ops_grouped_by_label(red):
    top = dict(red.top_ops(10))
    # unnamed ops group by their name's stem, summed per device
    assert top["fusion"] == pytest.approx((10 + 15 + 5 + 30) / 2 / 1e9)
    assert top["jit(_grouped_matmul_impl)/pallas_call"] == \
        pytest.approx(10 / 2 / 1e9)
    assert list(top)[0] == "fusion"
    assert tr.label(Op(0, 1, "fusion.9", "jit(train_step)/transpose(jvp())/"
                       "while/body/closed_call/shard_map/jit(a)/jit(b)/"
                       "pallas_call")) == \
        "transpose(jvp())/jit(a)/jit(b)/pallas_call"


def test_window_clips_ops():
    red = Reduced(_trace(), lo=30, hi=55)
    assert red.busy["/device:TPU:0"] == [(30, 40), (50, 55)]


def test_no_device_ops_is_an_error():
    with pytest.raises(ValueError, match="no device ops"):
        Reduced(Trace({}, [Op(0, 1, "place")]))


def test_load_reads_bench_spans(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.dispatch"):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    trace = tr.load(str(tmp_path))
    assert [s.name for s in trace.spans] == ["dispatch"]
    assert trace.spans[0].end > trace.spans[0].start


@pytest.mark.parametrize("text, name, opcode", [
    ("%pallas_vmem.36 = (bf16[8,16,1024,128]{3,2,1,0:T(8,128)(2,1)S(1)}, "
     "f32[8,16,1024,1]{3,2,1,0:T(8,128)}) custom-call(s32[1024,1]{1,0} "
     "%iota.229), custom_call_target=\"tpu_custom_call\"",
     "pallas_vmem.36", "custom-call"),
    ("%while.96 = (s32[]{:T(128)}, f32[]{:T(128)}, /*index=5*/f32[2,8,512]"
     "{2,1,0:T(8,128)}) while((s32[]{:T(128)}) %tuple.586), condition=%c",
     "while.96", "while"),
    ("%copy-start.56 = (f32[8,1024]{1,0:T(8,128)}, u32[]{:S(2)}) "
     "copy-start(f32[8,1024]{1,0:T(8,128)S(1)} %fusion.531)",
     "copy-start.56", "copy-start"),
    ("fusion.3", "fusion.3", ""),
])
def test_op_event_names_parse(text, name, opcode):
    assert tr.parse_op_name(text) == (name, opcode)
