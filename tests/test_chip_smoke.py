"""`chip_smoke.py` on the CPU at a tiny size: its NumPy serial reference
agrees with every engine's served run, and without a TPU the script
refuses to run and prints no result."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core import workloads as W
from repro.core.ingress import programs_from_batch

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


def _run(traffic, phases, budget):
    lines = []
    chip_smoke.run_phases(traffic, phases, budget=budget,
                          cache=chip_smoke.CacheEvents(),
                          require_kernels=False, out=lines.append)
    return lines


def test_smoke_phases_equal_serial_reference():
    traffic = chip_smoke.make_traffic(seed=3, n_records=512, n_txns=96)
    lines = _run(traffic, chip_smoke.ONE_CHIP_PHASES, budget=16)
    phases = [json.loads(l.split(" ", 1)[1]) for l in lines
              if l.startswith("phase {")]
    assert [(p["engine"], p["depth"]) for p in phases] == \
        list(chip_smoke.ONE_CHIP_PHASES)
    assert all(p["committed"] == 96 and p["batches"] == 6 for p in phases)
    by = {(p["engine"], p["depth"]): p for p in phases}
    assert by[("pcc", 0)]["fingerprint"] == by[("pogl", 0)]["fingerprint"]
    assert by[("pcc", 2)]["spec_executed"] > 0


@pytest.mark.parametrize("make", [W.genome_like, W.yada_like],
                         ids=["genome", "yada"])
def test_reference_follows_indirect_addressing(make):
    """Workloads whose addresses depend on the values read (indirect
    mode) and that read their own deferred writes."""
    wl = make(n_txns=48, n_lanes=4, seed=5)
    rng = np.random.default_rng(0)
    traffic = {"programs": programs_from_batch(wl.batch),
               "lanes": np.asarray(wl.lanes),
               "init": rng.integers(-50, 50, (wl.n_objects, 1),
                                    dtype=np.int32),
               "n_records": wl.n_objects}
    _run(traffic, (("pogl", 0), ("pcc", 0), ("destm", 0), ("occ", 0)),
         budget=16)


def test_reference_catches_a_wrong_store():
    traffic = chip_smoke.make_traffic(seed=1, n_records=256, n_txns=32)
    phase = chip_smoke.serve_phase(traffic, "pcc", 0, budget=16,
                                   cache=chip_smoke.CacheEvents(),
                                   require_kernels=False)
    store = phase["session"].store
    phase["session"].store = type(store)(
        values=store.values.at[0, 0].add(1), versions=store.versions,
        gv=store.gv)
    with pytest.raises(AssertionError, match="values differ"):
        chip_smoke.check_phase(phase, traffic, {})


def test_no_tpu_no_result():
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=REPO,
                       capture_output=True, text=True, timeout=120,
                       env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
