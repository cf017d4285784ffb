#!/usr/bin/env python3
"""Read the compared numbers of a cell over many seeds in one process.

    python3 chipbench/proof.py --workload <cell> --seeds 1,2,3 --mode program
    python3 chipbench/proof.py --workload <cell> --seeds 1,2,3 --mode control
    python3 chipbench/proof.py --workload <cell> --seeds 1,2,3 --mode fault:half_batch

This is how the limits in ``workloads/<cell>.json`` were set; the
benchmark's own runs never run it.  For each seed it prints one JSON line
with the readings of ``check.readings`` against the float32 reference of
the cell's configuration (its ``reference`` module):

``program``          the cell's timed path: ``program.Program`` built from
                     the seed and driven through its first steps, as
                     ``run.py`` does before its window;
``control``          the reference computed in float8
                     (``Reference(precision="fp8")``) in the
                     program's place;
``fault:<name>``     the program with one of :data:`FAULTS` planted in its
                     step (``exchange`` only on more than one chip).

It refuses to run off a TPU unless ``--cpu`` is given (rehearsals at a
small size).
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


@contextlib.contextmanager
def _wrapped_step(wrap):
    """The trainer's step built as ``wrap(real step)``."""
    from repro.launch import train
    real = train.make_train_step
    train.make_train_step = lambda *a, **k: wrap(real(*a, **k))
    try:
        yield
    finally:
        train.make_train_step = real


def _unchanged(step):
    def broken(state, batch, rng):
        return state, step(state, batch, rng)[1]
    return broken


def _half_batch(step):
    def broken(state, batch, rng):
        return step(state, {k: v[: v.shape[0] // 2] for k, v in
                            batch.items()}, rng)
    return broken


def _answer(step):
    """The step's update of the attention query weights, moved twice."""
    def broken(state, batch, rng):
        new, metrics = step(state, batch, rng)
        old_wq = state.params["blocks"][0]["attn"]["wq"]
        blocks = new.params["blocks"]
        attn = dict(blocks[0]["attn"])
        attn["wq"] = 2 * attn["wq"] - old_wq
        params = dict(new.params, blocks=(dict(blocks[0], attn=attn),)
                      + tuple(blocks[1:]))
        return new._replace(params=params), metrics
    return broken


@contextlib.contextmanager
def _no_exchange():
    """The program's all-to-alls replaced by the identity."""
    from repro.core import alltoall
    saved = alltoall.all_to_all, alltoall.grouped_all_to_all
    alltoall.all_to_all = lambda x, *a, **k: x
    alltoall.grouped_all_to_all = lambda x, counts, *a, **k: (x, counts)
    try:
        yield
    finally:
        alltoall.all_to_all, alltoall.grouped_all_to_all = saved


# the faults a training cell can have, each planted in the program:
# a step that returns its state unchanged; half of the batch left out
# (the mean taken over the rest); an answer altered where it is produced
# (one leaf's update doubled); the exchange between chips left out
FAULTS = {
    "unchanged": lambda: _wrapped_step(_unchanged),
    "half_batch": lambda: _wrapped_step(_half_batch),
    "answer": lambda: _wrapped_step(_answer),
    "exchange": _no_exchange,
}


def program_readings(cell, seed, devices):
    from chipbench import program, run
    prog = program.Program(cell, seed, devices=devices)
    read, _ = run.first_steps(prog)
    ring = prog.ring
    prog.free()
    del prog
    gc.collect()
    return read, ring


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--mode", required=True)
    ap.add_argument("--root", default=str(ROOT))
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args(argv)

    import jax
    from chipbench import check, run, spec, traffic
    from repro.launch.cache import enable_compile_cache

    root = pathlib.Path(args.root)
    cell = spec.load_cell(args.workload, root)
    devs = list(jax.devices()) if args.cpu else run.check_device(cell.chips)
    enable_compile_cache()
    reference = spec.module(cell.reference, root)
    kw = dict(shards=cell.chips, dropless=cell.dropless)
    ref = reference.Reference(cell.model, cell.workload["train"], **kw)
    fp8 = reference.Reference(cell.model, cell.workload["train"],
                              precision="fp8", **kw)
    steps = run.CHECK_STEPS
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        mode = args.mode
        ring = traffic.make_ring(cell.traffic, cell.model["vocab_size"], seed)
        if mode == "program":
            got, ring = program_readings(cell, seed, devs)
        elif mode == "control":
            got = fp8.train(seed, ring, steps, device=devs[0])
        elif mode.startswith("fault:") and mode[6:] in FAULTS:
            with FAULTS[mode[6:]]():
                got, ring = program_readings(cell, seed, devs)
        else:
            raise SystemExit(f"unknown mode {mode!r}")
        t1 = time.perf_counter()
        want = ref.train(seed, ring, steps, device=devs[0])
        read = check.readings(got, want)
        print(json.dumps({
            "cell": cell.name, "mode": mode, "seed": seed,
            "readings": {k: v["value"] for k, v in read.items()},
            "leaves": {k: v.get("leaf") for k, v in read.items()},
            "embed_scale": read["embed_row_gap"]["scale"],
            "embed_rows": read["embed_row_gap"]["rows"],
            "loss": got["loss"], "ref_loss": want["loss"],
            "grad_norm": got["grad_norm"], "ref_grad_norm": want["grad_norm"],
            "change": got["change"], "ref_change": want["change"],
            "seconds": [round(t1 - t0, 2),
                        round(time.perf_counter() - t1, 2)]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
