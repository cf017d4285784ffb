#!/usr/bin/env python3
"""Run one benchmark cell on the chips of this machine.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process: it refuses to run off a TPU (or on fewer chips than the cell
asks for, or with the kernels in interpret mode), turns on the persistent
compilation cache at its fixed path in the checkout, builds the cell's
trainer (``program.Program``) from the seed and compiles its step once,
drives the first steps through the step's own call (their losses,
gradients and parameter change are what the correctness check reads),
warms up, and then either measures for ``--seconds`` seconds (``--trace
0``: the cell's end-to-end metrics) or traces a short window (``--trace
1``: its per-layer metrics).  Both windows send about ``AHEAD_S``
seconds of steps ahead of the one whose metrics the host waits for, so
that the chip stays fed while the host stands still.  Then it frees the
program's state, runs the plain reference over the same first steps and
prints, as the last line of standard output, one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` (and
``breakdown`` when traced), the compared numbers coming last beside their
limits.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import collections  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import pathlib  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from types import SimpleNamespace  # noqa: E402
from typing import Dict, Optional, Tuple  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

CHECK_STEPS = 3      # the steps the reference follows
WARM_STEPS = 2       # further steps before the window
TRACE_STEPS = 10     # steps of the traced window
# seconds of steps sent ahead in a window; the TPU runtime may take
# fewer, and a send beyond what it holds waits in dispatch
AHEAD_S = 4.0


class Refused(RuntimeError):
    """This machine cannot run the cell: no result is printed."""


def check_device(chips: int):
    import jax
    from repro.kernels import ops as kops

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise Refused(f"needs a TPU; JAX found platform {devs[0].platform!r}")
    if len(devs) < chips:
        raise Refused(f"the cell needs {chips} chips; JAX found {len(devs)}")
    if kops.INTERPRET:
        raise Refused("the Pallas kernels are in interpret mode")
    return devs


def _change_norms(prog, p0) -> Dict[str, float]:
    """Per-leaf norms of the parameters' change since ``p0``."""
    import numpy as np
    p = prog.params_to_host()
    return {k: float(np.linalg.norm((p[k] - p0[k]).ravel())) for k in p}


def first_steps(prog) -> Tuple[Dict, float]:
    """Drive the program through its first steps, reading what the check
    compares.  Returns the readings and the seconds spent reading them
    (host copies of the state), which set-up does not count."""
    b1 = prog.tcfg.b1
    t = time.perf_counter()
    p0 = prog.params_to_host()
    spent = time.perf_counter() - t
    losses, grad = [], None
    for s in range(CHECK_STEPS):
        losses.append(prog.step(s)["loss"])
        if s == 0:
            t = time.perf_counter()
            grad = {k: v / (1 - b1)
                    for k, v in prog.first_moment_norms().items()}
            spent += time.perf_counter() - t
    t = time.perf_counter()
    change = _change_norms(prog, p0)
    del p0
    spent += time.perf_counter() - t
    return {"loss": losses, "grad_norm": grad, "change": change}, spent


def ahead_steps(step_s: float) -> int:
    """Steps that make ``AHEAD_S`` seconds at ``step_s`` seconds a step."""
    return max(1, math.ceil(AHEAD_S / step_s))


def timed_window(prog, start: int, seconds: float, ahead: int):
    """Steps from ``start``, sent until ``seconds`` have passed, with the
    host waiting for each step's metrics only once ``ahead`` later steps
    are in flight; then nothing more is sent and all that was sent is
    waited for.  Returns, for every step, the time from the previous
    step's metrics reaching the host (for the first, from the window's
    start) to its own; the window's seconds, up to the last step's
    metrics; and those metrics."""
    sent, arrived = collections.deque(), []
    s = start
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        sent.append(prog.dispatch(s))
        s += 1
        if len(sent) > ahead:
            m = prog.fetch(sent.popleft())
            arrived.append(time.perf_counter())
    while sent:
        m = prog.fetch(sent.popleft())
        arrived.append(time.perf_counter())
    times = [b - a for a, b in zip([t0] + arrived, arrived)]
    return times, arrived[-1] - t0, m


def traced_window(prog, start: int, ahead: int, trace_dir: str) -> None:
    """The timed window's loop in its steady state under the profiler:
    ``ahead`` steps are sent untraced, then each of ``1 + TRACE_STEPS``
    traced turns sends one step and waits for the oldest, every part of
    it marked by a ``bench.<part>`` host span on the trace's clock; the
    steps still in flight are waited for after the trace.  The first
    traced turn pays the profiler's own start and is left out of the
    reduction."""
    import jax
    sent = collections.deque(prog.dispatch(s)
                             for s in range(start, start + ahead))
    s = start + ahead
    jax.profiler.start_trace(trace_dir)
    try:
        for _ in range(1 + TRACE_STEPS):
            sent.append(prog.dispatch(s, annotate=True))
            s += 1
            prog.fetch(sent.popleft(), annotate=True)
    finally:
        jax.profiler.stop_trace()
        while sent:
            prog.fetch(sent.popleft())


def per_layer(cell, ctx) -> Dict[str, Dict]:
    from chipbench import spec
    out = {}
    for m in cell.per_layer:
        value = spec.metric_reader(m["name"], ctx.root)(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def memory_peak(devices) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def run(name: str, seed: int, seconds: float, trace: bool, *,
        root: pathlib.Path = ROOT, require_tpu: bool = True) -> Dict:
    """One run of cell ``name``; ``require_tpu=False`` skips the look for a
    chip (CPU tests at a small size)."""
    import jax
    from chipbench import check, hlo, program, reference, spec, tracereduce
    from repro.launch.cache import enable_compile_cache

    cell = spec.load_cell(name, root)
    devs = check_device(cell.chips) if require_tpu else jax.devices()
    print(f"device platform={devs[0].platform} kind={devs[0].device_kind} "
          f"count={len(devs)}", file=sys.stderr)
    enable_compile_cache()

    prog = program.Program(cell, seed, devices=devs)
    read_prog, check_s = first_steps(prog)
    step = CHECK_STEPS
    for _ in range(WARM_STEPS):
        t = time.perf_counter()
        prog.step(step)
        step_s = time.perf_counter() - t
        step += 1
    ahead = ahead_steps(step_s)
    print(f"steps sent ahead: {ahead} (a warm step took {step_s:.4f} s)",
          file=sys.stderr)
    setup_s = time.perf_counter() - T_START - check_s
    tokens = cell.batch * cell.seq
    if trace:
        with tempfile.TemporaryDirectory(prefix="chipbench-trace-") as tdir:
            traced_window(prog, step, ahead, tdir)
            tr = tracereduce.load(tdir)
        text = prog.compiled.as_text()
        places = [s for s in tr.spans if s.name == "place"]
        red = tracereduce.Reduced(tr, lo=places[1].start, hi=tr.spans[-1].end,
                                  names=hlo.op_names(text))
        ctx = SimpleNamespace(
            root=root, cell=cell, reduced=red, steps=TRACE_STEPS,
            window_s=red.window_s,
            memory=prog.compiled.memory_analysis(), hlo=text,
            chips=cell.chips,
            peaks=lambda: spec.peaks(devs[0].device_kind, root))
        metrics = per_layer(cell, ctx)
        attempted, failed = TRACE_STEPS, 0
        device_extra = {"busy_s": red.busy_s, "window_s": red.window_s}
        result_breakdown = {"device_ops": red.top_ops(10),
                            "idle_gaps": red.gaps(10)}
    else:
        before = prog.last["skipped"]
        times, window, last = timed_window(prog, step, seconds, ahead)
        attempted = len(times)
        failed = int(last["skipped"] - before)
        metrics = {
            "tokens_per_s_per_chip": {
                "value": attempted * tokens / window / cell.chips,
                "unit": "tokens/s"},
            "step_ms_p90": {
                "value": 1e3 * (statistics.quantiles(times, n=10)[-1]
                                if len(times) > 1 else times[0]),
                "unit": "ms"},
            "setup_s": {"value": setup_s, "unit": "s"}}
        device_extra, result_breakdown = {}, None
    peak = memory_peak(prog.devices)

    ring = prog.ring
    prog.free()
    del prog
    gc.collect()
    ref = reference.Reference(cell.model, cell.workload["train"],
                              shards=cell.chips, dropless=cell.dropless)
    read_ref = ref.train(seed, ring, CHECK_STEPS, device=devs[0])
    read = check.readings(read_prog, read_ref)
    limits = cell.limits
    correct = check.judge(read, limits)
    for line in check.lines(read, limits):
        print(line, file=sys.stderr)

    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics,
              "device": {"platform": devs[0].platform,
                         "kind": devs[0].device_kind, "count": len(devs),
                         "memory_peak_bytes": peak, **device_extra}}
    if result_breakdown is not None:
        result["breakdown"] = result_breakdown
    result["compared"] = {n: {"value": read[n]["value"],
                              "limit": limits.get(n)} for n in check.NUMBERS}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run(args.workload, args.seed, args.seconds, bool(args.trace))
    except Refused as e:
        print(f"chipbench: refused: {e}", file=sys.stderr)
        return 2
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
