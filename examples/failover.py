"""Crash a replica mid-stream, restore it, and watch it reconverge
bitwise (PR 9 — the paper's replica-fault-tolerance claim, §1, made
executable).

Pot's determinism is the whole fault-tolerance story: because the
serialization order is fixed BEFORE execution, a replica's state is a
pure function of (arrival journal, drain schedule).  So recovery needs
no coordination protocol — restore the latest crash-consistent snapshot,
feed the arrival-journal suffix the snapshot had not seen, and the
restarted replica lands on the SAME store fingerprint, the SAME commit
log, the SAME formed batches as a replica that never crashed.

Three acts:

1. **Replica A** serves the whole journal uninterrupted (a subprocess),
   snapshotting after every 2nd formed batch.
2. **Replica B** runs as a real subprocess (``python -m
   repro.core.checkpoint``) with a deterministic :class:`FaultPlan`:
   at formed batch 4, phase "execute", the process SIGKILLs itself —
   no cleanup, no goodbye (rc = -9).
3. **Replica B restarts** (a second subprocess) from B's snapshot
   directory + the shared arrival journal, and its summary payload —
   fingerprint, replay log, per-batch trace digests — is asserted
   bit-identical to A's.

Every replica is its own process and takes the accelerator in turn; this
parent only builds the arrival journal, on the host CPU, so it never
holds the device a replica needs.

Run:  PYTHONPATH=src python examples/failover.py
"""

import json
import os
import subprocess
import sys
import tempfile

import jax

jax.config.update("jax_platforms", "cpu")   # before anything touches JAX

from repro.core import IngressPool  # noqa: E402
from repro.core import workloads as W  # noqa: E402
from repro.core.checkpoint import snapshot_ids  # noqa: E402
from repro.core.ingress import programs_from_batch  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
N_OBJECTS, N_LANES = 64, 6

# -- the shared arrival stream: what replication actually ships ----------
wl = W.counters(n_txns=60, n_objects=N_OBJECTS, n_reads=2, n_writes=2,
                n_lanes=N_LANES, skew=0.7, seed=3)
source = IngressPool(capacity=512)
for i, program in enumerate(programs_from_batch(wl.batch)):
    source.admit(program, lane=i % N_LANES, fee=i % 5)
journal = source.arrival_journal()

kw = dict(n_objects=N_OBJECTS, engine="pcc", n_lanes=N_LANES,
          budgets=[7, 11], snapshot_every=2)

workdir = tempfile.mkdtemp(prefix="pot_failover_")
print(f"workdir: {workdir}")

# the replicas share the checkout's compile cache (or the one
# JAX_COMPILATION_CACHE_DIR names); cache every step, however quick
env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
env.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")


def drive(cfg, tag):
    cfg_path = os.path.join(workdir, f"{tag}.json")
    out_path = os.path.join(workdir, f"{tag}_out.json")
    with open(cfg_path, "w") as f:
        json.dump(cfg, f)
    r = subprocess.run(
        [sys.executable, "-m", "repro.core.checkpoint", cfg_path, out_path],
        env=env, cwd=REPO, capture_output=True, text=True)
    return r, out_path


# -- act 1: replica A, uninterrupted -------------------------------------
r, out_path = drive(dict(kw, journal=journal,
                         directory=os.path.join(workdir, "a")), "a")
assert r.returncode == 0, r.stderr[-2000:]
a = json.loads(open(out_path).read())
print(f"\nreplica A (uninterrupted): {a['batches_formed']} batches, "
      f"{a['snapshots_taken']} snapshots, "
      f"fingerprint 0x{a['fingerprint'] & 0xffffffff:08x}")

# -- act 2: replica B takes a SIGKILL at (batch 4, phase execute) --------
bdir = os.path.join(workdir, "b")
victim = dict(kw, journal=journal, directory=bdir,
              fault={"kill_batch": 4, "kill_phase": "execute"})
r, out_path = drive(victim, "victim")
assert r.returncode == -9 and not os.path.exists(out_path), r.stderr[-2000:]
print(f"\nreplica B: SIGKILLed at (batch 4, 'execute') — rc {r.returncode}, "
      f"snapshots on disk: {snapshot_ids(bdir)}")

# -- act 3: replica B restarts from its latest complete snapshot ---------
r, out_path = drive(dict(kw, journal=journal, directory=bdir, resume=True),
                    "recovery")
assert r.returncode == 0, r.stderr[-2000:]
out = json.loads(open(out_path).read())
print(f"replica B restarted: restored from snapshot {out['restored_from']}, "
      f"replayed {out['recovery_batches']} batches from the journal suffix, "
      f"fingerprint 0x{out['fingerprint'] & 0xffffffff:08x}")

assert out["fingerprint"] == a["fingerprint"]
assert out["replay_log"] == a["replay_log"]
assert out["trace_digests"] == \
    a["trace_digests"][len(a["trace_digests"]) - len(out["trace_digests"]):]
assert out["pool_depth"] == 0
print("\nrecovery ≡ uninterrupted: fingerprint, commit log and per-batch "
      "trace digests all bitwise identical — determinism IS the "
      "fault-tolerance protocol")
