"""A configuration of another architecture comes as new files: it names
its own reference and counts modules, the model check compares whatever
keys its file states, and every layer of a block pattern of any period
has leaves of its own names."""
import dataclasses
import json
from types import SimpleNamespace

import jax
import pytest

from cb_support import ROOT, make_tiny_root, no_compile_cache  # noqa: F401
from chipbench import program, run, spec

# a reference that is the default one, and marks that it was used
PROBE_REFERENCE = '''
import pathlib
from chipbench import reference as _default


class Reference(_default.Reference):
    def train(self, seed, batches, steps=3, device=None):
        pathlib.Path(__file__).with_suffix(".used").write_text(
            "f32" if self.q is None else "fp8")
        return super().train(seed, batches, steps, device)
'''
# counts whose step reads a number of its own
PROBE_COUNTS = '''
from chipbench.counts import flash_attention_work, grouped_matmul_work


def step_model_flops(model, batch, seq):
    return 1.5e12 * model["num_layers"]
'''


@pytest.fixture(scope="module")
def probe_root(tmp_path_factory):
    """The tiny root with a config that names the probe modules, and a
    cell on it."""
    root = make_tiny_root(tmp_path_factory.mktemp("probe"))
    bench = root / "chipbench"
    (bench / "reference_probe.py").write_text(PROBE_REFERENCE)
    (bench / "counts_probe.py").write_text(PROBE_COUNTS)
    cfg = json.loads((bench / "configs/tiny.json").read_text())
    cfg.update(name="tiny-probe", reference="reference_probe",
               counts="counts_probe")
    (bench / "configs/tiny-probe.json").write_text(json.dumps(cfg))
    (bench / "workloads/tiny-probe.json").write_text(
        (bench / "workloads/tiny-sort.json").read_text())
    top = json.loads((root / "BENCHMARK.json").read_text())
    top["configs"].append({"name": "tiny-probe", "source": "test",
                           "file": "chipbench/configs/tiny-probe.json",
                           "reduced": [], "why": "test"})
    top["workloads"].append({"name": "tiny-probe", "config": "tiny-probe",
                             "traffic": "tiny-b4", "chips": 1, "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(top))
    return root


def test_a_config_names_its_reference_and_counts(probe_root):
    cell = spec.load_cell("tiny-probe", probe_root)
    assert (cell.reference, cell.counts) == ("reference_probe",
                                             "counts_probe")
    paper = spec.load_cell("paper16e-switch-sort-1chip")
    assert (paper.reference, paper.counts) == ("reference", "counts")
    assert "reference" not in paper.config and "counts" not in paper.config


def test_run_checks_against_the_configs_reference(probe_root,
                                                  no_compile_cache):
    used = probe_root / "chipbench/reference_probe.used"
    res = run.run("tiny-probe", 5, 0.2, False, root=probe_root,
                  require_tpu=False)
    assert res["correct"], res["compared"]
    assert used.read_text() == "f32"


def test_step_mfu_reads_the_configs_counts(probe_root):
    cell = spec.load_cell("tiny-probe", probe_root)
    ctx = SimpleNamespace(
        cell=cell, steps=10, window_s=2.0, chips=1,
        counts=spec.module(cell.counts, probe_root),
        peaks=lambda: spec.peaks("TPU v5 lite", probe_root))
    mfu = spec.metric_reader("step_mfu", probe_root)(ctx)
    assert mfu == pytest.approx(100 * 3e12 * 10 / 2.0 / 197e12)


def test_a_missing_module_is_refused_before_the_run(probe_root):
    path = probe_root / "chipbench/configs/tiny-probe.json"
    saved = path.read_text()
    try:
        path.write_text(json.dumps(dict(json.loads(saved),
                                        reference="no_such_reference")))
        with pytest.raises(spec.SpecError, match="no_such_reference"):
            run.run("tiny-probe", 5, 0.2, False, root=probe_root,
                    require_tpu=False)
    finally:
        path.write_text(saved)


# -- the model check, on a config of another registered arch ---------------

SMOKE_OVERRIDES = {
    "num_layers": 2, "d_model": 128, "d_ff": 256, "vocab_size": 512,
    "attention": {"num_heads": 4, "num_kv_heads": 2, "head_dim": 32},
    "moe": {"num_experts": 4, "d_ff_expert": 256}}
LLAMA4_MODEL = {
    "num_layers": 2, "d_model": 128, "vocab_size": 512,
    "block_pattern": ["dense", "moe"], "num_heads": 4, "num_kv_heads": 2,
    "head_dim": 32, "qk_norm": True, "rope_theta": 500000.0, "act": "swiglu",
    "num_experts": 4, "num_shared_experts": 1, "gate": "switch",
    "experts_per_token": 1, "d_ff_expert": 256, "dtype": "bfloat16"}


def _cell(config, moe=None):
    return spec.Cell(name="c", chips=1, config=config, traffic_name="t",
                     traffic={}, workload={"moe": moe or {}})


def _llama4(**model):
    return _cell({"name": "llama4-smoke",
                  "arch": "llama4-maverick-400b-a17b",
                  "overrides": SMOKE_OVERRIDES,
                  "model": dict(LLAMA4_MODEL, **model)})


def test_model_check_takes_the_keys_a_file_states():
    cfg = program.model_config(_llama4())
    assert cfg.block_pattern == ("dense", "moe")
    assert cfg.moe.num_shared_experts == 1 and cfg.attention.qk_norm


@pytest.mark.parametrize("key,value", [("kv_lora_rank", 512),
                                       ("first_k_dense_replace", 1)])
def test_model_check_names_a_key_the_program_lacks(key, value):
    with pytest.raises(spec.SpecError, match=key):
        program.model_config(_llama4(**{key: value}))


@pytest.mark.parametrize("key,value", [("qk_norm", False),
                                       ("block_pattern", ["moe"]),
                                       ("num_shared_experts", 0),
                                       ("experts_per_token", 2)])
def test_model_check_names_a_differing_value(key, value):
    with pytest.raises(spec.SpecError, match=key):
        program.model_config(_llama4(**{key: value}))


@pytest.mark.parametrize("overrides,key", [
    ({"moe": {"num_shared_experts": 1}}, "num_shared_experts"),
    ({"block_pattern": ("dense", "moe")}, "block_pattern")])
def test_the_paper_file_holds_the_unstated_keys(overrides, key):
    paper = spec.load_cell("paper16e-switch-sort-1chip")
    config = dict(paper.config, overrides=overrides)
    with pytest.raises(spec.SpecError, match=key):
        program.model_config(_cell(config, paper.workload["moe"]))


# -- leaf names -----------------------------------------------------------

PAPER_LEAVES = ["embed", "final_norm", "lm_head"] + [
    f"layers.{l}.{k}" for l in (0, 1)
    for k in ("attn.wk", "attn.wo", "attn.wq", "attn.wv", "ln1", "ln2",
              "moe.gate_w", "moe.w_out", "moe.w_up")]


def _params(cfg):
    from repro.models import transformer
    return transformer.init_model(jax.random.PRNGKey(0), cfg)


def test_paper_leaf_names_are_unchanged():
    from repro import configs
    from chipbench import reference
    cell = spec.load_cell("paper16e-switch-sort-1chip")
    cfg = configs.smoke_config("hetumoe-paper-16e")
    leaves = program.canonical_leaves(_params(cfg))
    assert sorted(leaves) == sorted(PAPER_LEAVES)
    model = dict(cell.model, d_model=128, vocab_size=512, num_heads=4,
                 num_kv_heads=2, head_dim=32, num_experts=4, d_ff_expert=256)
    ref = reference.Reference(model, cell.workload["train"], shards=1,
                              dropless=False)
    want = jax.eval_shape(ref.init_params, 0)
    assert {k: v.shape for k, v in leaves.items()} == \
        {k: v.shape for k, v in want.items()}


def test_every_layer_of_a_period_two_pattern_has_its_own_leaves():
    from repro import configs
    cfg = configs.smoke_config("llama4-maverick-400b-a17b")
    cfg = dataclasses.replace(cfg, num_layers=6)          # three super-blocks
    params = _params(cfg)
    leaves = program.canonical_leaves(params)
    per_kind = {j: {".".join(str(getattr(k, "key", k)) for k in path)
                    for path, _ in jax.tree_util.tree_flatten_with_path(
                        params["blocks"][j])[0]}
                for j in range(len(cfg.block_pattern))}
    layers = [k for k in leaves if k.startswith("layers.")]
    assert len(layers) == len(set(layers)) == cfg.num_super_blocks * sum(
        len(v) for v in per_kind.values())
    for l in range(cfg.num_layers):
        mine = {k.split(".", 2)[2] for k in layers
                if k.split(".")[1] == str(l)}
        assert mine == per_kind[l % 2], l
    # layer 3 is the second super-block's MoE layer
    assert jax.numpy.array_equal(leaves["layers.3.moe.gate_w"],
                                 params["blocks"][1]["moe"]["gate_w"][1])
