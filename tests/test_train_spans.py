"""The trainer loop's host spans: every step of ``launch.train.run`` is a
``StepTraceAnnotation`` named ``train`` that holds its ``train.batch``,
``train.step`` and ``train.fetch`` spans, on the profiler's clock; an MoE
model's history carries the routing counters."""
import glob
import json
import os

import jax

from repro.launch import train

SPANS = ("train.batch", "train.step", "train.fetch")


def _host_events(trace_dir):
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    assert len(files) == 1, files
    data = jax.profiler.ProfileData.from_file(files[0])
    return [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
             dict(ev.stats))
            for plane in data.planes if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events
            if ev.name == "train" or ev.name.startswith("train.")]


def test_every_step_holds_its_spans(tmp_path):
    with jax.profiler.trace(str(tmp_path / "trace")):
        train.run("hetumoe-paper-16e", steps=3, batch=2, seq=16, smoke=True,
                  log_every=100)
    events = _host_events(str(tmp_path / "trace"))
    steps = sorted((e for e in events if e[0] == "train"),
                   key=lambda e: e[1])
    assert [int(e[3]["step_num"]) for e in steps] == [0, 1, 2]
    for _, lo, hi, _ in steps:
        inside = {n for n, a, b, _ in events
                  if n.startswith("train.") and lo <= a and b <= hi}
        assert inside >= set(SPANS), inside


def test_profile_traces_the_steps_after_the_first(tmp_path, capsys):
    hist = tmp_path / "hist.json"
    train.run("hetumoe-paper-16e", steps=train.PROFILE_SKIP + 2, batch=2,
              seq=16, smoke=True, log_every=1,
              profile_dir=str(tmp_path / "prof"), history_out=str(hist))
    events = _host_events(str(tmp_path / "prof"))
    traced = sorted(int(e[3]["step_num"]) for e in events if e[0] == "train")
    assert traced == [train.PROFILE_SKIP, train.PROFILE_SKIP + 1]
    out = capsys.readouterr().out
    assert " load " in out and " drop " in out
    for h in json.loads(hist.read_text())["history"]:
        assert 1.0 <= h["expert_load_ratio"] and 0 <= h["dropped_share"] < 1
