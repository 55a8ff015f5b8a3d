"""Train / eval / serve step builders.

Gating Dropout execution strategies (DESIGN.md §5):

  traced_cond -- ONE jitted step; the per-step consensus bit is computed
                 inside the graph from (seed, step) and fed to lax.cond.
  host_cond   -- TWO jitted steps (routed / dropped); the host draws the
                 same consensus bit and dispatches. The dropped executable
                 contains no all-to-all at all (paper-faithful).

Both strategies execute the MoE layers through the backend selected by
``cfg.moe.backend`` (oracle / sharded / pallas — the registry in
core/backend.py, DESIGN.md §6): the config is threaded into every jitted
step below via model_apply -> moe_apply, so swapping backends never
requires touching the step builders.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, TrainConfig
from repro.core.gating_dropout import drop_decision, drop_decision_host
from repro.core.moe import ParallelContext
from repro.models.model import model_apply
from repro.optim.adam import adam_init, adam_update

TrainState = Dict[str, Any]


def init_train_state(params, tc: TrainConfig) -> TrainState:
    return {"params": params, "opt": adam_init(params, tc),
            "step": jnp.zeros((), jnp.int32)}


def n_moe_layers(cfg: ModelConfig) -> int:
    if cfg.moe is None:
        return 0
    n = sum(1 for i in range(cfg.n_layers) if cfg.moe.is_moe_layer(i))
    if cfg.encdec is not None:
        n += sum(1 for i in range(cfg.encdec.n_encoder_layers)
                 if cfg.moe.is_moe_layer(i))
    return max(n, 1)


def xent_loss(logits: jax.Array, labels: jax.Array,
              mask: Optional[jax.Array]) -> Tuple[jax.Array, jax.Array]:
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    ll = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    if mask is None:
        mask = jnp.ones_like(ll)
    denom = jnp.maximum(mask.sum(), 1.0)
    loss = -(ll * mask).sum() / denom
    acc = ((logits.argmax(-1) == labels) * mask).sum() / denom
    return loss, acc


def chunked_xent(hidden: jax.Array, head: jax.Array, labels: jax.Array,
                 mask: Optional[jax.Array], chunk: int = 512
                 ) -> Tuple[jax.Array, jax.Array]:
    """Cross-entropy without materializing (B, L, V) f32 logits: scan over
    sequence chunks, recompute each chunk's logits in the backward
    (jax.checkpoint). Peak logits memory: (B, chunk, V)."""
    b, l, d = hidden.shape
    if mask is None:
        mask = jnp.ones((b, l), jnp.float32)
    if l <= 2 * chunk:
        logits = (hidden.astype(head.dtype) @ head).astype(jnp.float32)
        loss, acc = xent_loss(logits, labels, mask)
        return loss, acc
    pad = (-l) % chunk
    if pad:
        hidden = jnp.pad(hidden, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)))
        mask = jnp.pad(mask, ((0, 0), (0, pad)))
    n = hidden.shape[1] // chunk
    hc = hidden.reshape(b, n, chunk, d).transpose(1, 0, 2, 3)
    lc = labels.reshape(b, n, chunk).transpose(1, 0, 2)
    mc = mask.reshape(b, n, chunk).transpose(1, 0, 2)

    @jax.checkpoint
    def chunk_stats(hx, lx, mx):
        logits = (hx.astype(head.dtype) @ head).astype(jnp.float32)
        logp = jax.nn.log_softmax(logits, axis=-1)
        ll = jnp.take_along_axis(logp, lx[..., None], axis=-1)[..., 0]
        hit = (logits.argmax(-1) == lx) * mx
        return (ll * mx).sum(), hit.sum()

    def body(carry, xs):
        s, h = chunk_stats(*xs)
        return (carry[0] + s, carry[1] + h), None

    (ll_sum, hit_sum), _ = jax.lax.scan(
        body, (jnp.zeros(()), jnp.zeros(())), (hc, lc, mc))
    denom = jnp.maximum(mask.sum(), 1.0)
    return -ll_sum / denom, hit_sum / denom


def total_loss(params, batch, cfg: ModelConfig, ctx, *, rng, decision,
               is_training=True, frame=True):
    from repro.models.model import head_matrix
    hidden, aux = model_apply(params, batch, cfg, ctx, rng=rng,
                              decision=decision, is_training=is_training,
                              return_hidden=True)
    labels = batch["labels"]
    mask = batch.get("loss_mask")
    with jax.named_scope("lm_head"):
        head = head_matrix(params, cfg)
        loss, acc = chunked_xent(hidden, head, labels, mask,
                                 chunk=512 if cfg.scan_layers
                                 else hidden.shape[1])
    metrics = {"xent": loss, "acc": acc}
    nmoe = n_moe_layers(cfg)
    if cfg.moe is not None:
        bal = aux["balance"] / nmoe
        zl = aux["router_z"] / nmoe
        loss = loss + cfg.moe.balance_coef * bal + cfg.moe.router_z_coef * zl
        # comm_* are the substrate's in-graph transport counters
        # (DESIGN.md §10) summed over all MoE layers of THIS forward:
        # all-to-all ops, payload bytes, and per-device wire bytes the
        # step's forward pass moved (0 on Gate-Drop/local steps; the
        # backward pass doubles the wire, see comm/cost.py::step_cost)
        metrics.update(balance=bal, router_z=zl,
                       dropped_frac=aux["dropped_frac"] / nmoe,
                       comm_a2a_calls=aux["comm_a2a_calls"],
                       comm_bytes=aux["comm_bytes"],
                       comm_wire_bytes=aux["comm_wire_bytes"],
                       # §14 split: wire the chunked pipeline can hide
                       # behind expert compute vs the structurally
                       # exposed remainder (= wire for non-overlapped)
                       comm_exposed_bytes=aux["comm_exposed_bytes"],
                       comm_hidden_bytes=aux["comm_hidden_bytes"])
        if frame:
            # MetricsFrame router-health fields (DESIGN.md §15): the aux
            # values are already accumulated on device; surfacing them
            # only widens the fetched metric dict — the gate-drop
            # decision rate joins in make_train_step, where the step's
            # consensus bit is in scope
            metrics.update(expert_load=aux["load"] / nmoe,
                           router_entropy=aux["router_entropy"] / nmoe)
    if cfg.mtp and is_training and "mtp_hidden" in aux:
        labels2 = jnp.roll(labels, -1, axis=1)
        m2 = (mask if mask is not None else jnp.ones_like(labels, jnp.float32))
        m2 = m2 * jnp.roll(m2, -1, axis=1)
        m2 = m2.at[:, -1].set(0.0)
        with jax.named_scope("lm_head"):
            mtp_l, _ = chunked_xent(aux["mtp_hidden"], head, labels2, m2)
        loss = loss + 0.3 * mtp_l
        metrics["mtp_xent"] = mtp_l
    metrics["loss"] = loss
    return loss, metrics


def make_train_step(cfg: ModelConfig, tc: TrainConfig,
                    ctx: Optional[ParallelContext] = None,
                    *, jit: bool = True) -> Callable:
    """Returns train_step(state, batch, decision=None) -> (state, metrics).

    ``decision``: None -> computed in-graph from (seed, state.step)
    (traced_cond). Python bool -> baked into the executable (host_cond;
    jit caches one executable per value)."""

    frame = tc.metrics_frame

    def step_fn(state: TrainState, batch: Dict, decision) -> Tuple[TrainState, Dict]:
        step = state["step"]
        rng = jax.random.fold_in(jax.random.PRNGKey(tc.seed), step)
        if decision is None and cfg.moe is not None \
                and cfg.moe.gating_dropout.enabled:
            decision = drop_decision(cfg.moe.gating_dropout, tc.seed, step)
        grad_fn = jax.value_and_grad(
            lambda p, b, r: total_loss(p, b, cfg, ctx, rng=r,
                                       decision=decision, frame=frame),
            has_aux=True)
        k = max(tc.microbatches, 1)
        if k == 1:
            (loss, metrics), grads = grad_fn(state["params"], batch, rng)
        else:
            # gradient accumulation: scan over k microbatches (activation
            # memory / k); grads averaged, metrics averaged
            def split(x):
                b = x.shape[0]
                assert b % k == 0, (b, k)
                return x.reshape(k, b // k, *x.shape[1:])
            mb = jax.tree.map(split, batch)

            def acc_body(carry, xs):
                g_acc, m_acc, i = carry
                b_i = xs
                (_, m), g = grad_fn(state["params"], b_i,
                                    jax.random.fold_in(rng, i))
                g_acc = jax.tree.map(jnp.add, g_acc, g)
                m_acc = jax.tree.map(jnp.add, m_acc, m)
                return (g_acc, m_acc, i + 1), None

            (_, m0), g0 = grad_fn(state["params"],
                                  jax.tree.map(lambda x: x[0], mb),
                                  jax.random.fold_in(rng, 0))
            if cfg.scan_layers:
                (g_sum, m_sum, _), _ = jax.lax.scan(
                    acc_body, (g0, m0, 1),
                    jax.tree.map(lambda x: x[1:], mb))
            else:
                # unrolled for exact cost_analysis (scan bodies count once)
                carry = (g0, m0, 1)
                for i in range(1, k):
                    carry, _ = acc_body(
                        carry, jax.tree.map(lambda x: x[i], mb))
                g_sum, m_sum, _ = carry
            grads = jax.tree.map(lambda g: g / k, g_sum)
            metrics = jax.tree.map(lambda m: m / k, m_sum)
        with jax.named_scope("optimizer"):
            new_params, new_opt, opt_m = adam_update(grads, state["opt"],
                                                     state["params"], tc)
        metrics.update(opt_m)
        if frame and cfg.moe is not None:
            # the frame's gate-drop decision-rate field: the step's
            # consensus bit as 0/1 — traced under traced_cond, a baked
            # constant under host_cond, 0 with gating dropout off
            metrics["gate_dropped"] = (
                jnp.zeros((), jnp.float32) if decision is None
                else jnp.asarray(decision, jnp.float32))
        return {"params": new_params, "opt": new_opt, "step": step + 1}, metrics

    if jit:
        return jax.jit(step_fn, static_argnums=(2,), donate_argnums=(0,))
    return step_fn


def make_host_cond_steps(cfg: ModelConfig, tc: TrainConfig,
                         ctx: Optional[ParallelContext] = None):
    """The paper-faithful strategy: two executables + a host-side chooser.

    Usage:
        step = make_host_cond_steps(cfg, tc, ctx)
        state, m = step(state, batch, host_step)   # host_step: python int
    """
    inner = make_train_step(cfg, tc, ctx, jit=True)
    gd = cfg.moe.gating_dropout if cfg.moe is not None else None

    def step(state, batch, host_step: int):
        dec = drop_decision_host(gd, tc.seed, host_step) if gd else False
        return inner(state, batch, dec)

    return step


def make_eval_step(cfg: ModelConfig, ctx=None, *, jit: bool = True):
    def eval_fn(params, batch):
        _, metrics = total_loss(params, batch, cfg, ctx, rng=None,
                                decision=False, is_training=False)
        return metrics
    return jax.jit(eval_fn) if jit else eval_fn


# NOTE: the old make_serve_step (a per-token jitted decode_step wrapper)
# is gone — all generation runs through the compiled engine in
# repro.serve (DESIGN.md §7), which loops decode_step inside one jit.
