"""End-to-end driver: deterministic data-parallel training of a ~100M LM.

The full Pot configuration on a host-device mesh:
- every microbatch gradient is a preordered transaction (ordered commits);
- cross-shard reduction uses the fixed-ring deterministic schedule
  (optim/ordered_reduce.py) inside shard_map — bitwise-reproducible
  regardless of stragglers or restarts;
- checkpoints carry (params, opt, gv, data_step); restart resumes the
  identical serialization order;
- the run verifies determinism live: it re-executes step 1 at the end and
  asserts the recomputed parameters are bitwise identical.

Run (8 simulated devices):
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  PYTHONPATH=src python examples/train_lm.py --steps 300
"""

import argparse
import os
import sys
import time

if "--xla-devices" in sys.argv:
    n = sys.argv[sys.argv.index("--xla-devices") + 1]
    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={n}")

import jax
import jax.numpy as jnp
import numpy as np

from repro.ckpt import checkpoint as ck
from repro.data.pipeline import DataConfig, batch_at
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models import lm
from repro.models.config import ModelConfig
from repro.train.train_step import init_state, make_pot_dp_step


def build_config(scale: str) -> ModelConfig:
    if scale == "100m":
        return ModelConfig(
            name="pot-lm-100m", family="dense", n_layers=12, d_model=768,
            n_heads=12, n_kv_heads=4, d_ff=2048, vocab=32000,
            pattern=("attn",), mlp="swiglu")
    return ModelConfig(  # ~25m — quick CPU runs
        name="pot-lm-25m", family="dense", n_layers=8, d_model=512,
        n_heads=8, n_kv_heads=4, d_ff=1408, vocab=16384,
        pattern=("attn",), mlp="swiglu")


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--scale", choices=["25m", "100m"], default="25m")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--microbatches", type=int, default=2)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--ckpt-dir", default="/tmp/pot_lm_ckpt")
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--xla-devices", type=int, default=None)
    args = ap.parse_args()

    cfg = build_config(args.scale)
    n_dev = len(jax.devices())
    mesh = make_host_mesh(n_dev)
    print(f"model={cfg.name} params={cfg.param_count()/1e6:.1f}M "
          f"devices={n_dev}")

    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    state = init_state(params)
    dcfg = DataConfig(vocab=cfg.vocab, seq_len=args.seq,
                      global_batch=args.batch)
    n_mb = max(1, min(args.microbatches, args.batch // n_dev))
    step_fn = jax.jit(make_pot_dp_step(
        cfg, mesh, n_microbatches=n_mb, lr=3e-4))

    start = 0
    if args.resume and (last := ck.latest_step(args.ckpt_dir)) is not None:
        state, extra = ck.restore(args.ckpt_dir, last, state)
        start = extra["data_step"]
        print(f"resumed from step {start} (gv={int(state.gv)})")

    state_after_1 = None
    t0 = time.time()
    for i in range(start, args.steps):
        state, loss = step_fn(state, batch_at(dcfg, i))
        if i == 0:
            state_after_1 = jax.tree.map(np.asarray, state.params)
        if (i + 1) % 10 == 0 or i == start:
            dt = time.time() - t0
            print(f"step {i+1:4d}  loss {float(loss):.4f}  gv {int(state.gv)}"
                  f"  ({dt/(i-start+1):.2f}s/step)", flush=True)
        if (i + 1) % args.ckpt_every == 0:
            ck.save(args.ckpt_dir, i + 1, state,
                    extra={"data_step": i + 1})
            ck.prune(args.ckpt_dir, keep=2)

    # ---- live determinism audit: replay step 1 from scratch ----
    if start == 0 and state_after_1 is not None:
        replay = init_state(lm.init_params(jax.random.PRNGKey(0), cfg))
        replay, _ = step_fn(replay, batch_at(dcfg, 0))
        same = all(
            np.asarray(a).tobytes() == np.asarray(b).tobytes()
            for a, b in zip(jax.tree.leaves(state_after_1),
                            jax.tree.leaves(replay.params)))
        print(f"replayed step 1 bitwise-identical: {same}")
        assert same


if __name__ == "__main__":
    main()
