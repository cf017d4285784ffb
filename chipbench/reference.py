"""Plain float32 reference of a cell's model, loss and optimizer.

Written from the configuration file's numbers alone: it imports nothing of
the program and takes nothing it made.  It draws its own weights from the
seed with the same ``jax.random`` key splits the program's initialiser is
specified to use, so that the two start from the same weights.

The model: token embedding; per layer a pre-norm (RMSNorm, scale stored
as ``scale - 1``) causal multi-head attention with rotary positions, and
a pre-norm mixture-of-experts FFN (``relu(x W_up[e]) W_out[e]``) under a
switch (top-1) or GShard (top-2, second expert sampled in proportion to
its probability) gate, with capacity drops in slot-major token order
for a capacity-bound dispatch, and the Switch load-balancing loss;
final RMSNorm, output head, mean cross-entropy.  Then global-norm
clipping and AdamW with a linear-warmup cosine schedule.

Every matmul runs at ``Precision.HIGHEST`` in float32.  The experts run
densely over all tokens with each token's combine weight masking the
experts it was not routed to, in blocks of tokens under
``jax.checkpoint`` so that the whole step fits one chip.

``precision="fp8"`` is the control: every matmul operand of the compute
path rounded to float8 (e4m3, scaled per tensor to its largest value) and
the router's to bfloat16, the step below what the configuration states.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, List

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST
TOKEN_BLOCK = 2048          # tokens per checkpointed expert / head block


def _scaled(x: jax.Array, dtype) -> jax.Array:
    """``x`` rounded to ``dtype`` and back; to a float8 type after scaling
    the tensor so that its largest magnitude meets the type's largest."""
    if jnp.dtype(dtype) == jnp.bfloat16:
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / float(jnp.finfo(dtype).max)
    return (x / s).astype(dtype).astype(jnp.float32) * s


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _round(x: jax.Array, dtype) -> jax.Array:
    """A matmul operand at the control's precision: rounded to ``dtype``
    going forward, its cotangent rounded the same way (e5m2 in place of
    e4m3) going back, as an 8-bit float training path does."""
    return x if dtype is None else _scaled(x, dtype)


def _round_fwd(x, dtype):
    return _round(x, dtype), None


def _round_bwd(dtype, _, g):
    if dtype is None:
        return (g,)
    back = jnp.float8_e5m2 if jnp.dtype(dtype) == jnp.float8_e4m3fn else dtype
    return (_scaled(g, back),)


_round.defvjp(_round_fwd, _round_bwd)


class Reference:
    """The reference for one cell: ``model`` numbers (the config file),
    ``train`` settings (the cell file), token shards (chips) and whether
    the dispatch is dropless."""

    def __init__(self, model: Dict, train: Dict, *, shards: int,
                 dropless: bool, precision: str = "f32"):
        self.m = model
        self.t = train
        self.shards = shards
        self.dropless = dropless
        if precision not in ("f32", "fp8"):
            raise ValueError(precision)
        self.q = jnp.float8_e4m3fn if precision == "fp8" else None
        self.q_router = jnp.bfloat16 if precision == "fp8" else None

    # -- weights ----------------------------------------------------------
    def init_params(self, seed: int) -> Dict[str, jax.Array]:
        m = self.m
        d, V, E, f = m["d_model"], m["vocab_size"], m["num_experts"], \
            m["d_ff_expert"]
        H, KV, hd = m["num_heads"], m["num_kv_heads"], m["head_dim"]
        normal = lambda k, shape, scale: (
            jax.random.normal(k, shape, jnp.float32) * scale)
        k_embed, k_blocks, k_head, _ = jax.random.split(
            jax.random.PRNGKey(seed), 4)
        p = {"embed": normal(k_embed, (V, d), d ** -0.5),
             "lm_head": normal(k_head, (d, V), d ** -0.5),
             "final_norm": jnp.zeros((d,), jnp.float32)}
        for l, kl in enumerate(jax.random.split(k_blocks, m["num_layers"])):
            ks = jax.random.split(jax.random.split(kl, 1)[0], 6)
            ka = jax.random.split(ks[0], 4)
            km = jax.random.split(ks[1], 4)
            p.update({
                f"layers.{l}.ln1": jnp.zeros((d,), jnp.float32),
                f"layers.{l}.ln2": jnp.zeros((d,), jnp.float32),
                f"layers.{l}.attn.wq": normal(ka[0], (d, H * hd), d ** -0.5),
                f"layers.{l}.attn.wk": normal(ka[1], (d, KV * hd), d ** -0.5),
                f"layers.{l}.attn.wv": normal(ka[2], (d, KV * hd), d ** -0.5),
                f"layers.{l}.attn.wo": normal(ka[3], (H * hd, d),
                                              (H * hd) ** -0.5),
                f"layers.{l}.moe.gate_w": normal(km[0], (d, E), d ** -0.5),
                f"layers.{l}.moe.w_up": normal(km[1], (E, d, f), d ** -0.5),
                f"layers.{l}.moe.w_out": normal(km[3], (E, f, d), f ** -0.5),
            })
        return p

    # -- model ------------------------------------------------------------
    def _mm(self, spec: str, a, b, q=None):
        q = self.q if q is None else q
        return jnp.einsum(spec, _round(a, q), _round(b, q), precision=HIGHEST)

    def _rms(self, x, scale):
        ms = jnp.mean(x * x, axis=-1, keepdims=True)
        return x * lax.rsqrt(ms + self.m["norm_eps"]) * (1.0 + scale)

    def _rope(self, x, pos):
        half = x.shape[-1] // 2
        freq = self.m["rope_theta"] ** (
            -jnp.arange(0, half, dtype=jnp.float32) / half)
        ang = pos[:, None].astype(jnp.float32) * freq
        cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
        x1, x2 = x[..., :half], x[..., half:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)

    def _attention(self, p, l, h):
        """Causal multi-head attention, one batch row at a time."""
        m = self.m
        H, hd = m["num_heads"], m["head_dim"]
        S = h.shape[1]
        pos = jnp.arange(S)
        mask = pos[None, :] <= pos[:, None]

        @jax.checkpoint
        def row(hr):
            q = self._mm("sd,de->se", hr, p[f"layers.{l}.attn.wq"])
            k = self._mm("sd,de->se", hr, p[f"layers.{l}.attn.wk"])
            v = self._mm("sd,de->se", hr, p[f"layers.{l}.attn.wv"])
            q = self._rope(q.reshape(S, H, hd), pos)
            k = self._rope(k.reshape(S, H, hd), pos)
            v = v.reshape(S, H, hd)
            s = self._mm("qhd,khd->hqk", q, k) * hd ** -0.5
            s = jnp.where(mask[None], s, -jnp.inf)
            o = self._mm("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)
            return self._mm("se,ed->sd", o.reshape(S, H * hd),
                            p[f"layers.{l}.attn.wo"])

        return lax.map(row, h)

    def _route(self, logits, key):
        """Expert ids (N, K) and combine weights (N, K) of every token;
        tokens are sharded into ``shards`` contiguous groups, each
        sampling with its own key as the program's devices do."""
        m = self.m
        E = m["num_experts"]
        probs = jax.nn.softmax(logits, axis=-1)
        e1 = jnp.argmax(probs, axis=-1)
        g1 = jnp.take_along_axis(probs, e1[:, None], -1)[:, 0]
        if m["gate"] == "switch":
            return e1[:, None], g1[:, None], probs
        if m["gate"] != "gshard":
            raise ValueError(f"reference has no gate {m['gate']!r}")
        first = jax.nn.one_hot(e1, E, dtype=bool)
        masked = jnp.where(first, -jnp.inf, jnp.log(probs + 1e-9))
        T = logits.shape[0] // self.shards
        e2 = jnp.concatenate([
            jax.random.categorical(jax.random.fold_in(key, i),
                                   masked[i * T:(i + 1) * T], axis=-1)
            for i in range(self.shards)])
        g2 = jnp.take_along_axis(probs, e2[:, None], -1)[:, 0]
        den = g1 + g2 + 1e-9
        return (jnp.stack([e1, e2], -1), jnp.stack([g1 / den, g2 / den], -1),
                probs)

    def _keep(self, idx):
        """(N, K) mask of the assignments an expert's capacity keeps:
        per token shard, each expert takes its first ``C`` assignments in
        slot-major order (every first choice before any second one)."""
        N, K = idx.shape
        if self.dropless:
            return jnp.ones((N, K), bool)
        E = self.m["num_experts"]
        T = N // self.shards
        C = math.ceil(T * K / E * self.m["capacity_factor"])
        C = min(max(8, -(-C // 8) * 8), -(-(T * K) // 8) * 8)
        flat = idx.reshape(self.shards, T, K).transpose(0, 2, 1).reshape(
            self.shards, K * T)
        oh = jax.nn.one_hot(flat, E, dtype=jnp.int32)
        pos = jnp.sum((jnp.cumsum(oh, axis=1) - oh) * oh, axis=-1)
        keep = (pos < C).reshape(self.shards, K, T).transpose(0, 2, 1)
        return keep.reshape(N, K)

    def _experts(self, p, l, h, coef):
        """Sum over experts of ``coef[:, e] * relu(h W_up[e]) W_out[e]``."""
        E = self.m["num_experts"]
        w_up, w_out = p[f"layers.{l}.moe.w_up"], p[f"layers.{l}.moe.w_out"]

        @jax.checkpoint
        def block(args):
            hb, cb = args
            y = jnp.zeros_like(hb)
            for e in range(E):
                u = jax.nn.relu(self._mm("nd,df->nf", hb, w_up[e]))
                y = y + cb[:, e:e + 1] * self._mm("nf,fd->nd", u, w_out[e])
            return y

        N, d = h.shape
        nb = max(N // TOKEN_BLOCK, 1)
        y = lax.map(block, (h.reshape(nb, N // nb, d),
                            coef.reshape(nb, N // nb, E)))
        return y.reshape(N, d)

    def _moe(self, p, l, h, key):
        m = self.m
        E = m["num_experts"]
        B, S, d = h.shape
        x = h.reshape(B * S, d)
        logits = self._mm("nd,de->ne", x, p[f"layers.{l}.moe.gate_w"],
                          self.q_router)
        idx, w, probs = self._route(logits, key)
        keep = self._keep(idx)
        w = jnp.where(keep, w, 0.0)
        coef = jnp.sum(jax.nn.one_hot(idx, E) * w[..., None], axis=1)
        y = self._experts(p, l, x, coef)
        frac = jnp.mean(jax.nn.one_hot(idx[:, 0], E), axis=0)
        lb = E * jnp.sum(frac * jnp.mean(probs, axis=0))
        z = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
        aux = m["aux_loss_weight"] * lb + m["router_z_loss_weight"] * z
        return y.reshape(B, S, d), aux

    def loss(self, p, batch, key) -> jax.Array:
        """Mean cross-entropy over the masked tokens plus every layer's
        auxiliary loss, for step key ``key``."""
        m = self.m
        x = p["embed"][batch["inputs"]]
        aux = jnp.zeros((), jnp.float32)
        for l in range(m["num_layers"]):
            key, kl = jax.random.split(key)
            x = x + self._attention(p, l, self._rms(x, p[f"layers.{l}.ln1"]))
            y, a = self._moe(p, l, self._rms(x, p[f"layers.{l}.ln2"]), kl)
            x = x + y
            aux = aux + a
        x = self._rms(x, p["final_norm"])
        B, S, d = x.shape
        N = B * S
        nb = max(N // TOKEN_BLOCK, 1)

        @jax.checkpoint
        def ce(args):
            xb, tb, mb = args
            logits = self._mm("nd,dv->nv", xb, p["lm_head"])
            gold = jnp.take_along_axis(logits, tb[:, None], -1)[:, 0]
            return jnp.sum((jax.nn.logsumexp(logits, axis=-1) - gold) * mb)

        nll = lax.map(ce, (x.reshape(nb, N // nb, d),
                           batch["targets"].reshape(nb, N // nb),
                           batch["loss_mask"].reshape(nb, N // nb)))
        return jnp.sum(nll) / jnp.maximum(jnp.sum(batch["loss_mask"]), 1.0) \
            + aux

    # -- optimizer --------------------------------------------------------
    def lr(self, step: int) -> float:
        t = self.t
        lr, warm = t["learning_rate"], t["warmup_steps"]
        if step < warm:
            return lr * step / max(warm, 1)
        frac = min(max((step - warm) / max(t["total_steps"] - warm, 1), 0), 1)
        if t.get("schedule", "cosine") == "cosine":
            return lr * 0.5 * (1 + math.cos(math.pi * frac))
        return lr * (1 - frac)

    @functools.partial(jax.jit, static_argnums=0, donate_argnums=(1, 2))
    def _step(self, p, opt, batch, key, lr, count):
        t = self.t
        loss, g = jax.value_and_grad(self.loss)(p, batch, key)
        gn = jnp.sqrt(sum(jnp.sum(jnp.square(v)) for v in g.values()))
        g = {k: v * jnp.minimum(1.0, t["grad_clip"] / (gn + 1e-9))
             for k, v in g.items()}
        b1, b2 = t["b1"], t["b2"]
        c = count + 1.0
        bc1, bc2 = 1 - b1 ** c, 1 - b2 ** c
        mom = {k: b1 * opt["m"][k] + (1 - b1) * g[k] for k in p}
        vel = {k: b2 * opt["v"][k] + (1 - b2) * g[k] ** 2 for k in p}
        new = {k: p[k] - lr * ((mom[k] / bc1) / (jnp.sqrt(vel[k] / bc2)
                                                 + t["eps"])
                               + t["weight_decay"] * p[k]) for k in p}
        norms = {k: jnp.sqrt(jnp.sum(jnp.square(v))) for k, v in g.items()}
        return new, {"m": mom, "v": vel}, loss, norms

    def train(self, seed: int, batches: List[Dict[str, np.ndarray]],
              steps: int = 3, device=None) -> Dict:
        """``steps`` steps from the seed's weights on ``batches[:steps]``,
        step ``s`` keyed ``fold_in(PRNGKey(seed), s)``.  Returns each
        step's loss, the per-leaf norms of the first step's clipped
        gradient, and the per-leaf norms of the change of the parameters
        after the last step."""
        device = device or jax.devices()[0]
        with jax.default_device(device):
            p = self.init_params(seed)
            opt = {"m": {k: jnp.zeros_like(v) for k, v in p.items()},
                   "v": {k: jnp.zeros_like(v) for k, v in p.items()}}
            rng = jax.random.PRNGKey(seed)
            losses, grad_norms = [], None
            for s in range(steps):
                batch = {k: jnp.asarray(v) for k, v in batches[s].items()}
                p, opt, loss, norms = self._step(
                    p, opt, batch, jax.random.fold_in(rng, s),
                    jnp.float32(self.lr(s)), jnp.float32(s))
                losses.append(float(loss))
                if s == 0:
                    grad_norms = {k: float(v) for k, v in norms.items()}
            del opt
            p0 = self.init_params(seed)     # drawn again: one copy at a time
            change = {k: float(jnp.sqrt(jnp.sum(jnp.square(p[k] - p0[k]))))
                      for k in p}
        return {"loss": losses, "grad_norm": grad_norms, "change": change}
