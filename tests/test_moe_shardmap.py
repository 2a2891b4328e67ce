"""Numeric equivalence of the shard_map MoE (explicit EP all-to-all +
ZeRO-gathered experts) against the single-device dense oracle, executed
on a REAL multi-device mesh (subprocess with 8 host devices).

Run with a capacity factor high enough that no tokens drop: the two
paths then compute identical expert math and must agree to bf16
tolerance.  This is the test class that catches dispatch-layout bugs the
dry-run cannot (e.g. psum-ing partials across different token sets).

One or two tokens may flip their top-k expert choice between the two
paths: the router logits are computed under different reduction orders,
and a bf16 tie resolves differently.  Such a token gets a *different
but valid* expert mix (observed: 1 token of 128), so the
elementwise check allows outliers confined to at most 2 whole tokens —
every other token must pass the bf16 tolerance exactly.  A
dispatch-layout bug corrupts whole token SETS (a capacity slice, a
shard's worth), blowing both the token budget and the correlation gate
(> 0.999)."""

import os
import subprocess
import sys

CODE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_smoke_config
from repro.models import moe
from repro.launch.mesh import make_mesh
from repro.runtime.shardings import Profile, SMOKE

cfg = get_smoke_config("deepseek_moe_16b")
cfg = dataclasses.replace(cfg, capacity_factor=16.0)  # no drops
mesh = make_mesh((2, 4), ("data", "model"))
prof = Profile(data_axes=("data",), model_axis="model", mesh=mesh)

key = jax.random.PRNGKey(0)
p = moe.init_moe(key, cfg)
b, s = 4, 32
x = jax.random.normal(jax.random.PRNGKey(1), (b, s, cfg.d_model),
                      jnp.float32).astype(jnp.bfloat16)

dense = moe.moe_apply(p, x, cfg, SMOKE)

with jax.set_mesh(mesh):
    sharded = jax.jit(lambda p, x: moe.moe_apply(p, x, cfg, prof))(p, x)

a = np.asarray(dense, np.float32)
bv = np.asarray(sharded, np.float32)
# elementwise bf16 tolerance; outliers must be confined to <= 2 whole
# tokens (router tie-flips, see module docstring) — a real dispatch bug
# corrupts whole token sets and blows past this
bad = np.abs(a - bv) > (0.08 + 0.08 * np.abs(bv))
tokens_bad = bad.reshape(-1, a.shape[-1]).any(axis=1)
assert tokens_bad.sum() <= 2, (int(tokens_bad.sum()), int(bad.sum()))
# and the values must be meaningfully close overall (correlation)
corr = np.corrcoef(a.ravel(), bv.ravel())[0, 1]
assert corr > 0.999, corr
print("MOE_OK", corr)
"""


def test_shardmap_moe_matches_dense_oracle():
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run([sys.executable, "-c", CODE], capture_output=True,
                       text=True, cwd=repo,
                       env={**os.environ, "PYTHONPATH": "src"})
    assert "MOE_OK" in r.stdout, (r.stdout[-1000:], r.stderr[-3000:])
