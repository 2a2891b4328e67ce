"""Fig. 6 analog: speedup of a Pot fast transaction over the baseline
(speculative/instrumented) transaction, vs. access count and r/w mix.

The paper measures per-access overhead of read-set tracking, write
buffering and commit-time validation (§4.1.1, array-of-counters
microbenchmark).  We report the same quantity in both units available to
us: (a) exact instrumented-op counts from the cost model (deterministic),
and (b) measured CPU wall-time of the jitted engine in all-fast mode
(single non-conflicting txn = fast) vs. forced-speculative mode (txn
behind a conflicting predecessor)."""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, timeit
from repro.core import (RMW, READ, WRITE, make_batch, make_store,
                        pcc_execute)
from repro.core.metrics import _txn_cost
from repro.launch.compile_cache import enable_compile_cache


def run() -> None:
    store_n = 512
    for n_access in (0, 1, 2, 4, 8, 16, 32):
        for frac_w in (0.0, 0.5, 1.0):
            n_w = int(n_access * frac_w)
            n_r = n_access - n_w
            ins = [(READ, i, False, 0) for i in range(n_r)]
            ins += [(RMW, 64 + i, False, 1) for i in range(n_w)]
            ins = ins or [(READ, 0, False, 0)]
            cost_fast = float(_txn_cost(
                np.asarray([len(ins)]), np.asarray([n_r + n_w]),
                np.asarray([n_w]), fast=True)[0])
            cost_spec = float(_txn_cost(
                np.asarray([len(ins)]), np.asarray([n_r + n_w]),
                np.asarray([n_w]), fast=False)[0])
            speedup = cost_spec / cost_fast

            # wall-clock: engine with 1 txn (fast path, no validation)
            batch = make_batch([ins])
            store = make_store(store_n)
            seq = jnp.asarray([1], jnp.int32)
            t_fast = timeit(lambda: pcc_execute(store, batch, seq))
            # forced speculative: same txn behind a conflicting writer
            ins2 = [(WRITE, a, False, 9) for (_, a, _, _) in ins[:1]] or \
                [(WRITE, 0, False, 9)]
            batch2 = make_batch([ins2, ins])
            seq2 = jnp.asarray([1, 2], jnp.int32)
            t_spec = timeit(lambda: pcc_execute(store, batch2, seq2))
            emit(f"fig6_fast_tx[acc={n_access},w={frac_w:.1f}]",
                 t_fast * 1e6,
                 f"op_speedup={speedup:.2f}x spec_us={t_spec*1e6:.1f}")


if __name__ == "__main__":
    enable_compile_cache()
    run()
