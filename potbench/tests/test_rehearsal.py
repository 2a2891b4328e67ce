"""Each cell's run on the CPU at a tiny size: traffic from the seed, the
served path, the comparison with the reference; the control and each fault
planted under the served path must come out not correct.  ``run_cell`` is
the whole of a run after ``run.py``'s look for a chip."""

import os
import shutil
import subprocess
import sys
import time

import pytest

from potbench import harness, registry, trace
from potbench.tests import faults

BENCH = registry.benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]
ROOT = registry.ROOT


def tiny(cell_name: str) -> tuple[dict, dict]:
    """The cell's configuration and mix, cut to a size the CPU runs fast:
    4,096 records at most and batches of 16; an open mix offered more than
    one transaction a batch, so that batches form from several lanes."""
    cell = registry.cell(BENCH, cell_name)
    config = registry.config(BENCH, cell["config"])
    mix = registry.mix(cell["traffic"])
    if config["records"] > 4096:
        config["records"] = config["key_space"]["n_keys"] = 4096
    mix["budget"] = min(mix["budget"], 16)
    if mix["arrival"]["mode"] == "open":
        mix["arrival"]["knee_tps"] = 20000
    return config, mix


def run(cell_name: str, seed: int, **kw) -> dict:
    config, mix = tiny(cell_name)
    return harness.run_cell(BENCH, cell_name, seed, 1.0, False,
                            time.perf_counter(), config=config, mix=mix,
                            log=lambda *a, **k: None, **kw)


@pytest.mark.parametrize("cell_name", CELLS)
def test_cell_runs_correct(cell_name):
    out = run(cell_name, 2**31 + 11)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert all(c["value"] == 0 for c in out["checks"].values())
    assert list(out)[-1] == "checks"
    want = {m["name"] for m in registry.end_to_end_for(BENCH, cell_name)}
    assert set(out["metrics"]) == want
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert out["diagnostics"]["compiles_in_window"] == 0


@pytest.mark.parametrize("cell_name", CELLS)
def test_control_is_not_correct(cell_name):
    out = run(cell_name, 2**31 + 12, control="lane_order")
    assert not out["correct"]
    assert out["checks"]["commit_log_mismatches"]["value"] > 0


@pytest.mark.parametrize("fault", faults.FAULTS)
@pytest.mark.parametrize("cell_name", CELLS)
def test_fault_is_not_correct(cell_name, fault):
    out = run(cell_name, 2**31 + 13,
              prepare=lambda r: faults.plant(r, fault))
    assert not out["correct"]


def test_per_layer_readers_on_a_recorded_trace():
    """Every per-layer metric of the p2p cell reads a number from a record
    with the recorded profile of that cell."""
    red = trace.reduce(os.path.join(os.path.dirname(__file__), "data",
                                    "p2p_small.xplane.pb.gz"))
    record = {"window": {"committed": 3000, "lag_s": None},
              "spans": {"seconds": {"admit": 0.021, "drain": 0.02},
                        "txns": {"admit": 3000, "drain": 3000}},
              "counters": {"batches": 3, "rounds": 51, "walked_slots": 165888},
              "trace": red}
    for m in registry.per_layer_for(BENCH, "p2p.block1000.backlog"):
        v = registry.metric_reader(m["name"])(record)
        assert v is not None and v > 0, m["name"]
    for name in ("ingress.admit_lag_p95_ms.lat", "ingress.latency_p95_ms.lat"):
        assert registry.metric_reader(name)(record) is None


def _cli(cwd: str) -> subprocess.CompletedProcess:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "potbench/run.py", "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_cli_refuses_a_cpu():
    p = _cli(ROOT)
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_cli_refuses_without_the_program(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "potbench"), tmp_path / "potbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _cli(str(tmp_path))
    assert p.returncode != 0 and p.stdout.strip() == ""
