"""Plain reference of the training step the benchmark times.

An encoder-decoder sparse-expert transformer (Z-code M3, Kim et al. 2021)
trained with Gating Dropout (Liu et al. 2022), written out in
``jax.numpy`` at float32 with every matrix product at ``HIGHEST``
precision, and with no kernel, cache, scan or remat. It imports nothing
of the program. What it computes, per step:

* embeddings shared by both sides; the source adds sinusoidal positions;
* pre-norm layers (LayerNorm, eps 1e-6): self-attention with rotary
  positions on q and k (non-causal in the encoder, causal in the
  decoder), cross-attention in the decoder, then a GELU (tanh form)
  feed-forward, which on every ``moe_layer_period``-th layer is a top-1
  mixture of experts;
* the expert layer routes each group's tokens (one group per chip of the
  mesh, in token order) by a softmax router on inputs scaled by uniform
  jitter in [1 - eps, 1 + eps], keeps the first ``ceil(cf * T / E)``
  tokens of each expert, and weights each kept token's expert output by
  its router probability. On a Gate-Drop step each group routes only
  among its own chip's experts (all of them on one chip), capacity
  ``ceil(cf * T / E_local)``, and no balance loss is added;
* loss: mean cross-entropy over the target positions the mask counts,
  plus ``balance_coef`` times the mean over expert layers of the
  Switch balance loss E * sum_e f_e P_e, averaged over groups;
* Adam (no weight decay) after clipping the global gradient norm, with an
  inverse-square-root schedule after linear warm-up.

Departures from the published model, shared with the program and kept so
that the two compute the same function: attention does not mask padded
keys, and the router jitter is drawn in bfloat16 from keys folded from
(seed, step, layer, group), which the reference folds the same way.

``precision="fp8"`` is the control: every matrix product's operands, and
the gradients that flow back into them, are rounded to float8_e4m3 with
one scale per tensor.
"""
from __future__ import annotations

import math
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from weights import ModelSpec

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0


def _quant8(x: jax.Array) -> jax.Array:
    scale = jax.lax.stop_gradient(jnp.maximum(jnp.abs(x).max(), 1e-30)
                                  / F8_MAX)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


@jax.custom_vjp
def _q8(x):
    return _quant8(x)


def _q8_fwd(x):
    return _quant8(x), None


def _q8_bwd(_, g):
    return (_quant8(g),)


_q8.defvjp(_q8_fwd, _q8_bwd)


def make_mm(precision: str) -> Callable:
    """einsum at the reference's precision."""
    if precision == "f32":
        return lambda eq, a, b: jnp.einsum(eq, a, b, precision=HIGHEST)
    if precision == "fp8":
        return lambda eq, a, b: _q8(jnp.einsum(eq, _q8(a), _q8(b),
                                               precision=HIGHEST))
    raise ValueError(f"unknown precision {precision!r}")


def layer_norm(p, x, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                      * (x + 0.044715 * x ** 3)))


def sinusoidal(n: int, d: int) -> jax.Array:
    pos = jnp.arange(n, dtype=jnp.float32)[:, None]
    div = jnp.exp(jnp.arange(0, d, 2, dtype=jnp.float32)
                  * (-math.log(10000.0) / d))
    pe = jnp.zeros((n, d), jnp.float32)
    pe = pe.at[:, 0::2].set(jnp.sin(pos * div))
    return pe.at[:, 1::2].set(jnp.cos(pos * div[: d - d // 2]))


def rope(x, theta):
    """x: (B, L, H, hd); rotate the two halves of each head."""
    hd = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(p, x, src, *, causal, use_rope, theta, mm):
    q = mm("bld,dhk->blhk", x, p["wq"])
    k = mm("bld,dhk->blhk", src, p["wk"])
    v = mm("bld,dhk->blhk", src, p["wv"])
    if use_rope:
        q, k = rope(q, theta), rope(k, theta)
    s = mm("bqhd,bkhd->bhqk", q, k) / math.sqrt(q.shape[-1])
    if causal:
        n = x.shape[1]
        s = jnp.where(jnp.tril(jnp.ones((n, n), bool)), s, -1e30)
    o = mm("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
    return mm("blhk,hkd->bld", o, p["wo"])


def moe(p, x, spec: ModelSpec, *, groups: int, dropped: jax.Array,
        layer_key: Optional[jax.Array], mm, fault: str = ""
        ) -> Tuple[jax.Array, jax.Array]:
    """Top-1 expert layer over ``groups`` token groups; ``dropped`` (a bool
    or a traced one) picks the Gate-Drop branch. Returns (output, balance
    loss averaged over groups)."""
    b, l, d = x.shape
    xg = x.reshape(groups, b * l // groups, d)
    T = xg.shape[1]
    xr = xg
    if layer_key is not None and spec.jitter_eps > 0:
        lo, hi = 1.0 - spec.jitter_eps, 1.0 + spec.jitter_eps
        noise = jnp.stack([
            jax.random.uniform(jax.random.fold_in(layer_key, g), (T, d),
                               jnp.bfloat16, lo, hi)
            for g in range(groups)]).astype(jnp.float32)
        xr = xg * noise
    logits = jnp.einsum("gtd,de->gte", xr, p["router"]["w"],
                        precision=HIGHEST)
    branch = lambda drop: lambda: _route_and_apply(  # noqa: E731
        p, xg, logits, spec, groups, drop, mm, fault)
    if isinstance(dropped, bool):
        y, bal = branch(dropped)()
    else:
        y, bal = jax.lax.cond(dropped, branch(True), branch(False))
    return y.reshape(b, l, d), bal


def _route_and_apply(p, xg, logits, spec: ModelSpec, groups: int,
                     dropped: bool, mm, fault: str):
    G, T, d = xg.shape
    E, cf = spec.n_experts, spec.capacity_factor
    e_loc = E // groups if dropped else E
    if dropped:
        own = (jnp.arange(E)[None, :] // e_loc) == jnp.arange(groups)[:, None]
        logits = jnp.where(own[:, None, :], logits, -jnp.inf)
    cap = min(math.ceil(cf * T / e_loc), T)
    probs = jax.nn.softmax(logits, -1)
    top = jnp.argmax(probs, -1)                              # (G, T)
    onehot = jax.nn.one_hot(top, E, dtype=jnp.float32)       # (G, T, E)
    # rank of each token among its expert's tokens, in token order
    pos = ((jnp.cumsum(onehot, axis=1) - 1.0) * onehot).sum(-1)
    pos = pos.astype(jnp.int32)
    keep = pos < cap
    g = jnp.broadcast_to(jnp.arange(G)[:, None], top.shape)
    slot = jnp.where(keep, pos, cap)                         # cap: dropped
    buf = jnp.zeros((G, E, cap, d), xg.dtype).at[g, top, slot].add(
        xg, mode="drop")
    w_in, w_out = p["experts"]["w_in"], p["experts"]["w_out"]
    C = cap
    if fault == "no_exchange":
        # the exchange left out: a group's slots for expert e = i * n + r
        # meet expert g * n + r, the one in that slot on its own chip
        n = E // G
        buf = buf.reshape(G, G, n, C, d).transpose(0, 2, 1, 3, 4)
        buf = buf.reshape(1, E, G * C, d)
    h = gelu(mm("gecd,edf->gecf", buf, w_in))
    out = mm("gecf,efd->gecd", h, w_out)
    if fault == "no_exchange":
        out = out.reshape(G, n, G, C, d).transpose(0, 2, 1, 3, 4)
        out = out.reshape(G, E, C, d)
    w = jnp.take_along_axis(probs, top[..., None], -1)[..., 0]
    y = out[g, top, jnp.minimum(pos, cap - 1)] * (w * keep)[..., None]
    if dropped:
        bal = jnp.zeros((), jnp.float32)
    else:
        f = jax.lax.stop_gradient(onehot.mean(1))            # (G, E)
        bal = (E * (f * probs.mean(1)).sum(-1)).mean()
    return y, bal


def _ffn_or_moe(p, h, spec, *, groups, dropped, layer_key, mm, fault):
    if "moe" in p:
        return moe(p["moe"], h, spec, groups=groups, dropped=dropped,
                   layer_key=layer_key, mm=mm, fault=fault)
    f = p["ffn"]
    y = mm("blf,fd->bld", gelu(mm("bld,df->blf", h, f["w_in"])), f["w_out"])
    return y, jnp.zeros((), jnp.float32)


def loss_fn(params, batch, spec: ModelSpec, *, groups: int, dropped: jax.Array,
            step_key: Optional[jax.Array], precision: str = "f32",
            fault: str = "") -> jax.Array:
    """The step's loss: cross-entropy plus the weighted balance loss."""
    mm = make_mm(precision)
    eps, theta = spec.norm_eps, spec.rope_theta
    emb = params["embed"]
    enc_tok, dec_tok = batch["enc_tokens"], batch["tokens"]
    d = spec.d_model
    bal_sum = jnp.zeros((), jnp.float32)

    def lkey(i):
        return None if step_key is None else jax.random.fold_in(step_key, i)

    x = emb[enc_tok] + sinusoidal(enc_tok.shape[1], d)[None]
    for i, p in enumerate(params["enc"]):
        h = layer_norm(p["ln1"], x, eps)
        x = x + attention(p["attn"], h, h, causal=False, use_rope=True,
                          theta=theta, mm=mm)
        y, bal = _ffn_or_moe(p, layer_norm(p["ln2"], x, eps), spec,
                             groups=groups, dropped=dropped,
                             layer_key=lkey(i), mm=mm, fault=fault)
        x, bal_sum = x + y, bal_sum + bal
    enc = layer_norm(params["enc_final_norm"], x, eps)

    x = emb[dec_tok]
    for i, p in enumerate(params["dec"]):
        h = layer_norm(p["ln1"], x, eps)
        x = x + attention(p["attn"], h, h, causal=True, use_rope=True,
                          theta=theta, mm=mm)
        h = layer_norm(p["ln_cross"], x, eps)
        x = x + attention(p["cross"], h, enc, causal=False, use_rope=False,
                          theta=theta, mm=mm)
        y, bal = _ffn_or_moe(p, layer_norm(p["ln2"], x, eps), spec,
                             groups=groups, dropped=dropped,
                             layer_key=lkey(i), mm=mm, fault=fault)
        x, bal_sum = x + y, bal_sum + bal
    x = layer_norm(params["final_norm"], x, eps)
    logits = mm("bld,dv->blv", x, params["lm_head"])
    logp = jax.nn.log_softmax(logits, -1)
    ll = jnp.take_along_axis(logp, batch["labels"][..., None], -1)[..., 0]
    mask = batch["loss_mask"]
    xent = -(ll * mask).sum() / jnp.maximum(mask.sum(), 1.0)
    return xent + spec.balance_coef * bal_sum / spec.n_moe_layers


def lr_at(step: jax.Array, opt: Dict) -> jax.Array:
    """Inverse square root after linear warm-up; ``step`` counts from 1."""
    s = jnp.maximum(step.astype(jnp.float32), 1.0)
    w = float(opt["warmup_steps"])
    return opt["lr"] * jnp.minimum(s / w, jnp.sqrt(w / jnp.maximum(s, w)))


def adam(params, grads, m, v, step, opt: Dict):
    """One Adam step on clipped gradients. Returns (params, m, v, clipped
    grads)."""
    leaves = jax.tree.leaves(grads)
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in leaves))
    scale = jnp.minimum(1.0, opt["grad_clip"] / jnp.maximum(gnorm, 1e-9))
    grads = jax.tree.map(lambda g: g * scale, grads)
    b1, b2 = opt["b1"], opt["b2"]
    t = step.astype(jnp.float32)
    lr = lr_at(step, opt)
    m = jax.tree.map(lambda a, g: b1 * a + (1 - b1) * g, m, grads)
    v = jax.tree.map(lambda a, g: b2 * a + (1 - b2) * g * g, v, grads)
    bc1, bc2 = 1 - b1 ** t, 1 - b2 ** t
    params = jax.tree.map(
        lambda p, a, c: p - lr * (a / bc1) / (jnp.sqrt(c / bc2) + opt["eps"]),
        params, m, v)
    return params, m, v, grads


def decision(consensus_seed: int, step: int, rate: float) -> bool:
    """Gating Dropout's per-step consensus bit: a Bernoulli(rate) draw
    from the step folded into the run's seed (Liu et al. 2022 §3, drawn
    the same on every host so no broadcast is needed)."""
    key = jax.random.fold_in(jax.random.PRNGKey(consensus_seed ^ 0x6A7ED0),
                             step)
    return bool(jax.device_get(jax.random.bernoulli(key, rate)))


def half_batch(batch):
    """The half-batch fault: the second half of the rows left out."""
    return jax.tree.map(lambda a: a[: a.shape[0] // 2], batch)


def make_step(spec: ModelSpec, opt: Dict, *, groups: int, precision: str,
              fault: str = "", dropped: Optional[bool] = None,
              shardings: Any = None, batch_sharding: Any = None) -> Callable:
    """jit(params, m, v, batch, step, step_key, dropped) -> (params, m,
    v, loss, per-leaf norms of the clipped gradient). ``dropped`` given
    here is baked in, and the argument of that name is ignored; a sharded
    step takes it so, since a traced branch keeps the partitioner from
    splitting what the branches compute."""

    def step_fn(params, m, v, batch, step, step_key, drop):
        if fault == "half_batch":
            batch = half_batch(batch)
        loss, grads = jax.value_and_grad(loss_fn)(
            params, batch, spec, groups=groups,
            dropped=drop if dropped is None else dropped,
            step_key=step_key, precision=precision, fault=fault)
        params, m, v, grads = adam(params, grads, m, v, step, opt)
        return params, m, v, loss, leaf_norms(grads)

    if shardings is None:
        return jax.jit(step_fn, donate_argnums=(0, 1, 2))
    return jax.jit(step_fn, donate_argnums=(0, 1, 2),
                   in_shardings=(shardings, shardings, shardings,
                                 batch_sharding, None, None, None),
                   out_shardings=(shardings, shardings, shardings, None,
                                  None))


def leaf_norms(tree) -> List[jax.Array]:
    return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
            for x in jax.tree.leaves(tree)]


def run_reference(spec: ModelSpec, opt: Dict, params0_fn: Callable,
                  batches: List[Dict], decisions: List[bool],
                  consensus_seed: int, *, groups: int, precision: str = "f32",
                  fault: str = "", shardings: Any = None,
                  batch_sharding: Any = None) -> Dict[str, Any]:
    """The first ``len(batches)`` steps from the weights ``params0_fn()``
    makes. Returns the per-step losses and the per-leaf norms of the
    first step's clipped gradient and of the parameters' change over all
    steps."""
    params = params0_fn()
    zeros = jax.jit(lambda t: jax.tree.map(jnp.zeros_like, t),
                    out_shardings=shardings)
    m, v = zeros(params), zeros(params)
    steps: Dict[Any, Callable] = {}
    losses, g1 = [], None
    base = jax.random.PRNGKey(consensus_seed)
    for i, (batch, dec) in enumerate(zip(batches, decisions)):
        static = None if shardings is None else dec
        if static not in steps:
            steps[static] = make_step(
                spec, opt, groups=groups, precision=precision, fault=fault,
                dropped=static, shardings=shardings,
                batch_sharding=batch_sharding)
        params, m, v, loss, gnorms = steps[static](
            params, m, v, batch, jnp.asarray(i + 1, jnp.int32),
            jax.random.fold_in(base, i), jnp.asarray(dec))
        losses.append(loss)
        if i == 0:
            g1 = gnorms
    del m, v
    p0 = params0_fn()
    change = jax.jit(lambda a, b: leaf_norms(jax.tree.map(jnp.subtract,
                                                          a, b)))(params, p0)
    return {"loss": [float(x) for x in jax.device_get(losses)],
            "grad_norm": [float(x) for x in jax.device_get(g1)],
            "change_norm": [float(x) for x in jax.device_get(change)]}
