"""Training substrate: loss falls, grad-accum equivalence, CE chunking,
optimizer math, checkpoint roundtrip."""
import re
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.core.config import TrainConfig
from repro.data import SyntheticLM
from repro.checkpoint import restore_checkpoint, save_checkpoint
from repro.models import transformer as T
from repro.optim import adamw_update, clip_by_global_norm, init_opt_state, make_schedule
from repro.training import chunked_ce_loss, make_train_step
from repro.training.train_step import init_train_state

RNG = jax.random.PRNGKey(0)


def test_loss_decreases_moe(mesh1):
    cfg = configs.smoke_config("dbrx-132b")
    tcfg = TrainConfig(learning_rate=3e-3, warmup_steps=5, total_steps=25)
    state = init_train_state(RNG, cfg, tcfg)
    ds = SyntheticLM(cfg, batch=8, seq_len=32)
    step = jax.jit(make_train_step(cfg, tcfg, mesh1), donate_argnums=(0,))
    losses = []
    for s in range(25):
        state, m = step(state, ds.next_batch(s), jax.random.fold_in(RNG, s))
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] - 0.3, (losses[0], losses[-1])


def test_grad_accum_equivalence(mesh1):
    """microbatches=2 produces the same update as microbatches=1."""
    cfg = configs.smoke_config("starcoder2-3b").replace(dtype="float32")
    t1 = TrainConfig(total_steps=2, warmup_steps=0, microbatches=1)
    t2 = TrainConfig(total_steps=2, warmup_steps=0, microbatches=2)
    s0 = init_train_state(RNG, cfg, t1)
    ds = SyntheticLM(cfg, batch=4, seq_len=16)
    b = ds.next_batch(0)
    s1, m1 = jax.jit(make_train_step(cfg, t1, mesh1))(s0, b, RNG)
    s2, m2 = jax.jit(make_train_step(cfg, t2, mesh1))(s0, b, RNG)
    np.testing.assert_allclose(float(m1["ce"]), float(m2["ce"]), rtol=1e-5)
    a = jax.tree.leaves(s1.params)
    c = jax.tree.leaves(s2.params)
    for x, y in zip(a, c):
        # loose rtol/atol: the two microbatch schedules sum gradients in
        # a different order; f32 accumulation noise leaves O(1/65536)
        # elements past rtol=1e-3 (observed max abs diff ~8e-6 on values
        # ~5e-3) — not a bug, so don't chase bit-exactness.
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=2e-3, atol=1e-5)


def _ce_cfg(head):
    """untied: yi's own lm_head; tied: gemma2's embedding transposed,
    without its softcap; softcap: gemma2 as configured (tied, cap 30)."""
    if head == "untied":
        return configs.smoke_config("yi-6b")
    cfg = configs.smoke_config("gemma2-9b")
    return cfg if head == "softcap" else cfg.replace(final_softcap=None)


def _full_ce(w, cfg, h, t, m):
    """Plain cross-entropy over the full (B, S, V) logits, in f32."""
    logits = h @ w
    if cfg.final_softcap:
        logits = cfg.final_softcap * jnp.tanh(logits / cfg.final_softcap)
    nll = (jax.nn.logsumexp(logits, axis=-1)
           - jnp.take_along_axis(logits, t[..., None], axis=-1)[..., 0])
    return jnp.sum(nll * m) / jnp.maximum(jnp.sum(m), 1.0)


@pytest.mark.parametrize("masked", [False, True], ids=["all", "holes"])
@pytest.mark.parametrize("nc", [1, 2, 8])
@pytest.mark.parametrize("head", ["untied", "tied", "softcap"])
def test_chunked_ce_equals_full(mesh1, head, nc, masked):
    """The chunked, fused CE against the full-logits one: the loss, and
    the gradients in h and in the head weight (for a tied head, the
    embedding's, through the transpose), at the cotangent 1 and at the
    dynamic loss scale's 2^15, which scales them exactly."""
    cfg = _ce_cfg(head)
    p = T.init_model(RNG, cfg)
    B, S = 2, 32
    kh, kt, km = jax.random.split(jax.random.PRNGKey(1), 3)
    h = jax.random.normal(kh, (B, S, cfg.d_model), jnp.float32)
    t = jax.random.randint(kt, (B, S), 0, cfg.vocab_size)
    m = (jax.random.uniform(km, (B, S)) > 0.3 if masked
         else jnp.ones((B, S), bool)).astype(jnp.float32)
    assert 0 < float(m.sum()) < B * S or not masked

    loss, pull = jax.vjp(
        lambda p, h: chunked_ce_loss(p, cfg, h, t, m, mesh1, num_chunks=nc),
        p, h)
    w = T.head_weight(p, cfg)
    ref, (rw, rh) = jax.value_and_grad(_full_ce, (0, 2))(w, cfg, h, t, m)
    np.testing.assert_allclose(float(loss), float(ref), rtol=1e-5)

    gp, gh = pull(jnp.float32(1.0))
    np.testing.assert_allclose(np.asarray(gh), np.asarray(rh),
                               rtol=1e-4, atol=1e-7)
    np.testing.assert_allclose(np.asarray(T.head_weight(gp, cfg)),
                               np.asarray(rw), rtol=1e-4, atol=1e-7)

    scale = 2.0 ** 15
    sp, sh = pull(jnp.float32(scale))
    np.testing.assert_array_equal(np.asarray(sh), np.asarray(gh) * scale)
    for a, b in zip(jax.tree.leaves(sp), jax.tree.leaves(gp)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b) * scale)


def _vocab_dots(hlo_text: str, vocab: int) -> list:
    """The dot instructions of a compiled module with an operand that has
    a dimension of size ``vocab``."""
    shapes = dict(re.findall(r"%([\w.\-]+) = \w+\[([\d,]*)\]", hlo_text))
    dots = re.findall(r"%([\w.\-]+) = \S+ dot\(%([\w.\-]+), %([\w.\-]+)\)",
                      hlo_text)
    return [d for d, *ops in dots
            if any(str(vocab) in shapes.get(o, "").split(",") for o in ops)]


@pytest.mark.parametrize("nc", [2, 8])
def test_chunked_ce_grad_does_not_recompute_logits(mesh1, nc):
    """Compiled value_and_grad of the CE holds three vocabulary-sized
    matmuls: the logits, dh and dW, all in the forward scan.  A fourth
    is the logits recomputed in the backward.  Without a gradient, the
    jitted loss equals the fused forward's."""
    cfg = configs.smoke_config("yi-6b")
    assert cfg.vocab_size not in (cfg.d_model, 2, 32 // nc)
    p = T.init_model(RNG, cfg)
    B, S = 2, 32
    h = jax.random.normal(RNG, (B, S, cfg.d_model), jnp.bfloat16)
    t = jax.random.randint(RNG, (B, S), 0, cfg.vocab_size)
    m = jnp.ones((B, S)).at[:, :3].set(0.0)
    loss = lambda p, h: chunked_ce_loss(p, cfg, h, t, m, mesh1, num_chunks=nc)
    vg = jax.jit(jax.value_and_grad(loss, (0, 1)))
    text = vg.lower(p, h).compile().as_text()
    assert len(_vocab_dots(text, cfg.vocab_size)) == 3, \
        _vocab_dots(text, cfg.vocab_size)
    fused, _ = vg(p, h)
    np.testing.assert_allclose(float(jax.jit(loss)(p, h)), float(fused),
                               rtol=1e-6)


def test_adamw_against_reference():
    """One AdamW step vs a hand-rolled numpy reference."""
    tcfg = TrainConfig(learning_rate=1e-2, weight_decay=0.1)
    p = {"w": jnp.array([1.0, -2.0, 3.0])}
    g = {"w": jnp.array([0.1, 0.2, -0.3])}
    st = init_opt_state(p, tcfg)
    newp, newst = adamw_update(g, st, p, tcfg, jnp.asarray(1e-2))
    m = 0.1 * np.asarray(g["w"])
    v = 0.05 * np.asarray(g["w"]) ** 2
    mh, vh = m / 0.1, v / 0.05
    ref = np.asarray(p["w"]) - 1e-2 * (mh / (np.sqrt(vh) + tcfg.eps)
                                       + 0.1 * np.asarray(p["w"]))
    np.testing.assert_allclose(np.asarray(newp["w"]), ref, rtol=1e-5)


def test_grad_clip():
    g = {"a": jnp.ones((4,)) * 10.0}
    clipped, gn = clip_by_global_norm(g, 1.0)
    np.testing.assert_allclose(float(gn), 20.0, rtol=1e-5)
    np.testing.assert_allclose(
        float(jnp.linalg.norm(clipped["a"])), 1.0, rtol=1e-4)


def test_schedule_warmup_and_decay():
    tcfg = TrainConfig(learning_rate=1e-3, warmup_steps=10, total_steps=100)
    sched = make_schedule(tcfg)
    assert float(sched(jnp.asarray(0))) == 0.0
    np.testing.assert_allclose(float(sched(jnp.asarray(10))), 1e-3, rtol=1e-3)
    assert float(sched(jnp.asarray(100))) < 1e-5


def test_checkpoint_roundtrip(mesh1):
    cfg = configs.smoke_config("rwkv6-1.6b")
    tcfg = TrainConfig()
    state = init_train_state(RNG, cfg, tcfg)
    with tempfile.TemporaryDirectory() as d:
        save_checkpoint(d, state, 7)
        state2, step = restore_checkpoint(d, state)
    assert step == 7
    for a, b in zip(jax.tree.leaves(state.params),
                    jax.tree.leaves(state2.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_bf16_moments_mode():
    tcfg = TrainConfig(optimizer_state_dtype="bfloat16")
    p = {"w": jnp.ones((8, 8))}
    st = init_opt_state(p, tcfg)
    assert st["m"]["w"].dtype == jnp.bfloat16
    newp, newst = adamw_update({"w": jnp.ones((8, 8)) * 0.1}, st, p, tcfg,
                               jnp.asarray(1e-3))
    assert newst["v"]["w"].dtype == jnp.bfloat16
    assert bool(jnp.all(jnp.isfinite(newp["w"])))
