"""Training step: baseline (traditional) vs Pot (preordered commits).

Gradient application is the framework's highest-volume transaction.  Two
step flavors:

- ``baseline``: one global-batch gradient; GSPMD chooses the cross-shard
  reduction schedule (the *traditional transactions* regime — outcome
  bitwise-depends on reduction scheduling/timing on real fleets).
- ``pot``: every microbatch gradient is a preordered transaction.
  In-chip, microbatch grads accumulate by ordered commits (fixed
  sequence order, ``lax.scan`` + ordered pairwise tree).  Cross-shard,
  when ``det_reduce`` is on (pure-DP meshes), the reduction runs on the
  fixed-ring schedule of optim/ordered_reduce.py inside shard_map.  The
  optimizer apply is the fast-mode direct commit (kernels/fused_adamw on
  TPU; the jnp twin here), and ``gv`` stamps the commit — checkpoint/
  restart resumes the same serialization order (ckpt/checkpoint.py).
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from repro.models import lm
from repro.models.config import ModelConfig
from repro.optim import (adafactor_init, adafactor_update, adamw_init,
                         adamw_update, ordered_ring_reduce)
from repro.runtime.shardings import Profile


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class TrainState:
    params: dict
    opt: dict
    gv: jax.Array      # () int32 — global version (last committed txn)
    step: jax.Array    # () int32


def init_state(params, optimizer="adamw"):
    init = adamw_init if optimizer == "adamw" else adafactor_init
    return TrainState(params=params, opt=init(params),
                      gv=jnp.zeros((), jnp.int32),
                      step=jnp.zeros((), jnp.int32))


def loss_fn(params, batch, cfg: ModelConfig, prof: Profile, *, chunk=0,
            unroll=False, remat=True):
    """Next-token CE.  batch: {tokens (B,S), labels (B,S)} plus optional
    {frames} (whisper) / {patches} (internvl)."""
    enc = None
    prefix = batch.get("patches")
    if cfg.encoder_layers:
        enc = lm.encode(params, batch["frames"], cfg, prof, unroll=unroll,
                        remat=remat)
    logits = lm.forward(params, batch["tokens"], cfg, prof,
                        prefix_embeds=prefix, enc=enc, chunk=chunk,
                        unroll=unroll, remat=remat)
    off = logits.shape[1] - batch["labels"].shape[1]
    logits = logits[:, off:].astype(jnp.float32)
    labels = batch["labels"]
    mask = labels >= 0
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(
        logits, jnp.clip(labels, 0)[..., None], axis=-1)[..., 0]
    nll = (logz - gold) * mask
    return nll.sum() / jnp.maximum(mask.sum(), 1)


def _split_microbatches(batch, n):
    return jax.tree.map(
        lambda a: a.reshape((n, a.shape[0] // n) + a.shape[1:]), batch)


def make_train_step(cfg: ModelConfig, prof: Profile, *, optimizer="adamw",
                    mode: str = "baseline", n_microbatches: int = 1,
                    chunk=0, unroll=False, remat=True, lr=1e-3, wd=0.01,
                    grad_specs=None, accum_dtype=jnp.float32):
    """Build a jittable train step.  mode: "baseline" | "pot".
    grad_specs: optional PartitionSpec tree matching params — pins the
    gradient (and microbatch accumulator) sharding to the parameter
    sharding so the accumulation scan never carries replicated leaves."""
    upd = adamw_update if optimizer == "adamw" else adafactor_update
    kwargs = {"lr": lr, "wd": wd} if optimizer == "adamw" else {"lr": lr}
    grad_fn = jax.value_and_grad(
        partial(loss_fn, cfg=cfg, prof=prof, chunk=chunk, unroll=unroll,
                remat=remat))

    def pin(grads):
        if grad_specs is None:
            return grads
        return jax.tree.map(
            lambda g, s: jax.lax.with_sharding_constraint(g, s),
            grads, grad_specs)

    def baseline_step(state: TrainState, batch):
        loss, grads = grad_fn(state.params, batch)
        grads = pin(grads)
        params, opt = upd(state.params, grads, state.opt, **kwargs)
        return dataclasses.replace(
            state, params=params, opt=opt, step=state.step + 1), loss

    def pot_step(state: TrainState, batch):
        if n_microbatches > 1:
            mbs = _split_microbatches(batch, n_microbatches)

            # ordered commits: microbatch transactions accumulate in the
            # sequencer-fixed order (scan order == sequence order); every
            # commit is a fixed-order float add -> bitwise deterministic.
            def commit(carry, mb):
                acc, loss_acc = carry
                loss, g = grad_fn(state.params, mb)
                acc = pin(jax.tree.map(
                    lambda a, b: a + b.astype(accum_dtype), acc, g))
                return (acc, loss_acc + loss), None

            zeros = pin(jax.tree.map(
                lambda p: jnp.zeros(p.shape, accum_dtype), state.params))
            (gsum, loss_sum), _ = jax.lax.scan(
                commit, (zeros, jnp.zeros((), jnp.float32)), mbs)
            grads = jax.tree.map(lambda g: g / n_microbatches, gsum)
            loss = loss_sum / n_microbatches
        else:
            loss, grads = grad_fn(state.params, batch)
            grads = pin(grads)

        # fast-mode direct commit (kernels/fused_adamw on TPU)
        params, opt = upd(state.params, grads, state.opt, **kwargs)
        return dataclasses.replace(
            state, params=params, opt=opt, gv=state.gv + 1,
            step=state.step + 1), loss

    return pot_step if mode == "pot" else baseline_step


def make_pot_dp_step(cfg: ModelConfig, mesh, *, axis="data",
                     optimizer="adamw", n_microbatches: int = 1,
                     lr=1e-3, wd=0.01, remat=False):
    """Fully-deterministic pure-DP Pot step (the end-to-end configuration
    of examples/train_lm.py).

    The entire step runs inside shard_map over ``axis``: each shard
    computes its local-batch gradient (a preordered transaction; the
    sequencer order is the ring position), gradients cross shards via the
    fixed-ring ordered reduction (bitwise deterministic regardless of
    arrival order / stragglers), and every shard applies the identical
    fast-mode commit.  Params/opt replicated (pure DP)."""
    from jax.sharding import PartitionSpec as P

    prof = Profile(enabled=False)
    upd = adamw_update if optimizer == "adamw" else adafactor_update
    kwargs = {"lr": lr, "wd": wd} if optimizer == "adamw" else {"lr": lr}
    n_shards = mesh.shape[axis]
    grad_fn = jax.value_and_grad(
        partial(loss_fn, cfg=cfg, prof=prof, remat=remat))

    def local_step(state: TrainState, batch):
        if n_microbatches > 1:
            mbs = _split_microbatches(batch, n_microbatches)

            def commit(carry, mb):
                acc, la = carry
                loss, g = grad_fn(state.params, mb)
                return (jax.tree.map(
                    lambda a, b: a + b.astype(jnp.float32), acc, g),
                    la + loss), None

            zeros = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), state.params)
            (gsum, ls), _ = jax.lax.scan(
                commit, (zeros, jnp.zeros((), jnp.float32)), mbs)
            grads = jax.tree.map(lambda g: g / n_microbatches, gsum)
            loss = ls / n_microbatches
        else:
            loss, grads = grad_fn(state.params, batch)
        # ordered commit across shards: fixed-ring deterministic sum
        grads = jax.tree.map(
            lambda g: ordered_ring_reduce(g, axis) / n_shards, grads)
        loss = ordered_ring_reduce(loss[None], axis)[0] / n_shards
        params, opt = upd(state.params, grads, state.opt, **kwargs)
        return dataclasses.replace(
            state, params=params, opt=opt, gv=state.gv + 1,
            step=state.step + 1), loss

    def step(state: TrainState, batch):
        sspec = jax.tree.map(lambda _: P(), state)
        bspec = jax.tree.map(lambda _: P(axis), batch)
        f = jax.shard_map(local_step, mesh=mesh, in_specs=(sspec, bspec),
                      out_specs=(sspec, P()), check_vma=False)
        return f(state, batch)

    return step
