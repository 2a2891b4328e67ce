"""Mixture-of-Experts block (arctic-480b: 128e top-2 + dense residual;
deepseek-moe-16b: 64e top-6 + 2 shared experts, fine-grained).

Two dispatch paths:

- **shard_map path** (prof.mesh set — dry-run / production): the GShard
  schedule written explicitly.  Each (data × seq-over-model) shard routes
  its own tokens locally, scatters into per-expert capacity slots, and a
  real ``lax.all_to_all`` over the model axis exchanges expert blocks
  (EP).  Expert weights are FSDP-stored over data and ZeRO-gathered at
  use (backward of the gather = the grad reduce-scatter); each chip then
  runs its E_loc experts at full width — correct for any token layout
  (an F-Megatron split over data would psum partials from different
  token sets).  GSPMD cannot be trusted to derive this schedule from a
  scatter (it replicates the token stream); writing it with explicit
  collectives is both faster and gives the roofline true all-to-all
  byte counts.  Numeric equivalence vs. the dense path is tested on a
  real multi-device mesh in tests/test_moe_shardmap.py.
- **dense path** (no mesh — CPU smoke tests): same math, local scatter.

Dropping: per-shard capacity = ceil(tokens·k/E · capacity_factor), the
standard GShard bound (documented in DESIGN.md §6).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.blocks import C, _cast, init_mlp, mlp_apply, mlp_specs
from repro.models.config import ModelConfig
from repro.runtime.shardings import Profile, cons


def init_moe(key, cfg: ModelConfig):
    d, f, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    ks = jax.random.split(key, 5)
    std = d ** -0.5
    p = {
        "router": jax.random.normal(ks[0], (d, e), jnp.float32) * std,
        "w1": jax.random.normal(ks[1], (e, d, f), jnp.float32) * std,
        "w3": jax.random.normal(ks[2], (e, d, f), jnp.float32) * std,
        "w2": jax.random.normal(ks[3], (e, f, d), jnp.float32) * std,
    }
    if cfg.n_shared_experts:
        sub = jax.random.split(ks[4], 2)[1]
        p["shared"] = init_mlp(sub, cfg, d_ff=cfg.n_shared_experts * f)
    if cfg.dense_residual:
        p["residual"] = init_mlp(ks[4], cfg, d_ff=cfg.residual_d_ff)
    return p


def moe_specs(cfg: ModelConfig, prof: Profile):
    p = {"router": P(None, None),
         "w1": prof.experts_in(), "w3": prof.experts_in(),
         "w2": prof.experts_out()}
    if cfg.n_shared_experts:
        p["shared"] = mlp_specs(cfg, prof)
    if cfg.dense_residual:
        p["residual"] = mlp_specs(cfg, prof)
    return p


def _route_and_dispatch(xt, router, e, k, cf):
    """Local routing: xt (T, D) -> (x_e (E, C, D), eidx, pos, keep, gate)."""
    t, d = xt.shape
    logits = (xt @ router.astype(xt.dtype)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, eidx = jax.lax.top_k(probs, k)                    # (T, k)
    gate = gate / jnp.clip(gate.sum(-1, keepdims=True), 1e-9)

    cap = max(1, int(t * k / e * cf))
    flat_e = eidx.reshape(-1)                               # (T*k,)
    oh = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)
    pos = ((jnp.cumsum(oh, axis=0) - 1) * oh).sum(-1)       # (T*k,)
    keep = pos < cap
    src = jnp.repeat(xt, k, axis=0)                         # (T*k, D)
    w8 = jnp.where(keep, 1.0, 0.0).astype(xt.dtype)[:, None]
    x_e = jnp.zeros((e, cap, d), xt.dtype)
    x_e = x_e.at[flat_e, jnp.where(keep, pos, 0)].add(src * w8,
                                                      mode="drop")
    return x_e, flat_e, pos, keep, gate


def _combine(y_e, flat_e, pos, keep, gate, t, k, d):
    gath = y_e[flat_e, jnp.where(keep, pos, 0)]             # (T*k, D)
    gath = gath * jnp.where(keep, 1.0, 0.0).astype(y_e.dtype)[:, None]
    gath = gath * gate.reshape(-1)[:, None].astype(y_e.dtype)
    return gath.reshape(t, k, d).sum(axis=1)


def _expert_ffn(x_e, w1, w3, w2):
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", x_e, w1)) \
        * jnp.einsum("ecd,edf->ecf", x_e, w3)
    return jnp.einsum("ecf,efd->ecd", h, w2)


def moe_apply(p, x, cfg: ModelConfig, prof: Profile):
    """x (B, S, D) -> (B, S, D)."""
    p = _cast(p)
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k

    if prof.enabled and prof.mesh is not None:
        routed = _moe_shardmap(p, x, cfg, prof)
    else:
        xt = x.reshape(b * s, d)
        x_e, flat_e, pos, keep, gate = _route_and_dispatch(
            xt, p["router"], e, k, cfg.capacity_factor)
        y_e = _expert_ffn(x_e, p["w1"], p["w3"], p["w2"])
        routed = _combine(y_e, flat_e, pos, keep, gate, b * s, k,
                          d).reshape(b, s, d)

    out = routed
    if "shared" in p:
        out = out + mlp_apply(p["shared"], x, cfg, prof)
    if "residual" in p:
        out = out + mlp_apply(p["residual"], x, cfg, prof)
    return out


def _moe_shardmap(p, x, cfg: ModelConfig, prof: Profile):
    """Explicit GShard schedule (see module docstring)."""
    e, k = cfg.n_experts, cfg.top_k
    da, ma = prof.da, prof.ma
    mesh = prof.mesh

    def local(xl, router, w1, w3, w2):
        # xl (B_loc, S_loc, D) — tokens local to this (data, model) shard
        bl, sl, d = xl.shape
        xt = xl.reshape(bl * sl, d)
        x_e, flat_e, pos, keep, gate = _route_and_dispatch(
            xt, router, e, k, cfg.capacity_factor)
        # EP: exchange expert blocks over the model axis
        x_e = jax.lax.all_to_all(x_e, prof.model_axis, split_axis=0,
                                 concat_axis=1, tiled=True)
        # ZeRO: gather this chip's E_loc experts' weights over data (FSDP
        # storage); the backward of the gather is the grad reduce-scatter.
        # (An F-Megatron split over data would psum partials computed
        # from DIFFERENT data rows' tokens — incorrect in this layout.)
        ax = (prof.data_axes if len(prof.data_axes) > 1
              else prof.data_axes[0])
        w1 = jax.lax.all_gather(w1, ax, axis=1, tiled=True)
        w3 = jax.lax.all_gather(w3, ax, axis=1, tiled=True)
        w2 = jax.lax.all_gather(w2, ax, axis=1, tiled=True)
        y_e = _expert_ffn(x_e, w1.astype(C), w3.astype(C), w2.astype(C))
        y_e = jax.lax.all_to_all(y_e, prof.model_axis, split_axis=1,
                                 concat_axis=0, tiled=True)
        y = _combine(y_e, flat_e, pos, keep, gate, bl * sl, k, d)
        return y.reshape(bl, sl, d)

    fs = prof._fs(0)
    ep = prof.model_axis   # experts always EP over the model axis
    w_in_spec = (P(ep, fs, None), P(ep, fs, None), P(ep, fs, None))
    # tokens enter sequence-sharded over the model axis (when divisible):
    # every chip routes DISTINCT tokens and the all-to-all carries unique
    # blocks — replicating over model would do n_model× redundant
    # dispatch/compute.
    n_ma = mesh.shape[prof.model_axis]
    seq_ax = ma if (ma is not None and x.shape[1] % n_ma == 0
                    and x.shape[1] >= n_ma) else None
    f = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(da, seq_ax, None), P(None, None)) + w_in_spec,
        out_specs=P(da, seq_ax, None),
        check_vma=False)
    return f(x, p["router"], p["w1"], p["w3"], p["w2"])
