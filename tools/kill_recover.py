"""Kill-and-recover drill for the federated training driver.

Three subprocess runs of ``repro.launch.train`` on the same problem:

* **A** (reference): uninterrupted, checkpointing every round.
* **B** (victim): identical flags plus ``--kill-at-round k`` — the
  server SIGKILLs itself *mid-round k* (client compute done, update not
  applied), exactly the preemption window the checkpoint protocol must
  survive.  The run must die with ``-SIGKILL`` and leave
  ``ckpt_latest.msgpack`` at round ``k``.
* **C** (recovery): ``--resume`` from B's checkpoint dir, running to the
  same ``--rounds``.

Then the drill asserts B's latest checkpoint is at round ``k`` and that
C's final checkpoint is **bit-identical** to A's: every array leaf, the
round counter, the CommLog byte totals, the per-client data pointers,
the VPCS flags and the eval history.  A SIGKILL costs zero information.
``--sample-frac``/``--quantize`` run the same drill under fleet-scale
client sampling and a quantized uplink: the survivor must restore the
sampler's RNG state (checkpoint meta ``sampler``, compared bit-for-bit
below) so it re-draws the killed round's cohort identically.

Mesh-reshape recovery: ``--mesh-b 2x2`` runs the victim sharded on a
2x2 FLShardPlan while A and C stay unsharded (or pick any combination
with ``--mesh-a/--mesh-c``) — checkpoints are mesh-portable, so the
survivor may restore onto a different topology than the one that died.
Each subprocess forces its own host device count from its ``--mesh``
flag, so the drill itself needs no XLA_FLAGS.

A CPU harness: the three runs get ``JAX_PLATFORMS=cpu`` unless the
caller set it, so they never contend for an accelerator.

CI runs::

    PYTHONPATH=src python tools/kill_recover.py --rounds 4 --kill-at 2

Exit code 0 iff every check passes; ``--json PATH`` writes the report.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import signal
import subprocess
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np

from repro.checkpoint.io import load_manifest
from repro.checkpoint.state import FINAL_NAME, LATEST_NAME

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def train_cmd(a, ckpt_dir: str, *, mesh=None, kill_at=None, resume=False):
    cmd = [sys.executable, "-m", "repro.launch.train",
           "--arch", a.arch, "--method", a.method,
           "--rounds", str(a.rounds), "--T", str(a.T),
           "--clients", str(a.clients), "--batch", str(a.batch),
           "--seed", str(a.seed), "--eval-every", str(a.eval_every),
           "--checkpoint-dir", ckpt_dir, "--checkpoint-every", "1"]
    if a.sample_frac < 1.0:
        cmd += ["--sample-frac", str(a.sample_frac)]
    if a.quantize != "none":
        cmd += ["--quantize", a.quantize]
    if mesh:
        cmd += ["--mesh", mesh]
    if kill_at is not None:
        cmd += ["--kill-at-round", str(kill_at)]
    if resume:
        cmd += ["--resume"]
    return cmd


def run(cmd, label: str) -> subprocess.CompletedProcess:
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    env.setdefault("JAX_PLATFORMS", "cpu")
    env.pop("XLA_FLAGS", None)  # each child forces its own device count
    print(f"[{label}] {' '.join(cmd)}")
    p = subprocess.run(cmd, cwd=REPO, env=env, capture_output=True,
                       text=True, timeout=1800)
    tail = "\n".join(p.stdout.strip().splitlines()[-3:])
    print(f"[{label}] rc={p.returncode}\n{tail}")
    if p.returncode not in (0, -signal.SIGKILL):
        print(p.stderr[-2000:], file=sys.stderr)
    return p


def compare_finals(path_a: str, path_c: str) -> dict:
    """Bit-compare two server checkpoints: every leaf + the replay-
    relevant meta."""
    meta_a, leaves_a = load_manifest(path_a)
    meta_c, leaves_c = load_manifest(path_c)
    checks = {"leaf_sets_equal": set(leaves_a) == set(leaves_c)}
    diff = [k for k in leaves_a
            if k in leaves_c and not np.array_equal(leaves_a[k],
                                                    leaves_c[k])]
    checks["leaves_bitmatch"] = checks["leaf_sets_equal"] and not diff
    for field in ("round", "up_bytes", "down_bytes", "ptrs",
                  "early_stopped", "history", "pending", "sampler"):
        checks[f"meta_{field}_equal"] = meta_a.get(field) == meta_c.get(field)
    if diff:
        checks["first_diff_leaf"] = diff[0]
    return checks


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tiny")
    ap.add_argument("--method", default="random",
                    help="space method (random is fast; see launch/train.py)")
    ap.add_argument("--rounds", type=int, default=4)
    ap.add_argument("--kill-at", type=int, default=2,
                    help="round the victim run SIGKILLs itself in")
    ap.add_argument("--T", type=int, default=2)
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--eval-every", type=int, default=2)
    ap.add_argument("--sample-frac", type=float, default=1.0,
                    help="run the drill under fleet-scale client sampling "
                         "(the survivor must restore the sampler RNG state "
                         "to re-draw the killed round's cohort)")
    ap.add_argument("--quantize", default="none",
                    help="run the drill under a quantized uplink codec "
                         "(none|int8|int4[-nearest])")
    ap.add_argument("--mesh-a", default=None, help="mesh for the reference")
    ap.add_argument("--mesh-b", default=None,
                    help="mesh for the killed run (e.g. 2x2: die sharded, "
                         "recover unsharded)")
    ap.add_argument("--mesh-c", default=None, help="mesh for the recovery")
    ap.add_argument("--workdir", default=None,
                    help="keep checkpoints here (default: tempdir)")
    ap.add_argument("--json", default=None, help="write report here")
    a = ap.parse_args()
    if not 0 < a.kill_at < a.rounds:
        ap.error("--kill-at must be inside (0, --rounds)")

    work = a.workdir or tempfile.mkdtemp(prefix="kill_recover_")
    dir_a, dir_b = os.path.join(work, "ref"), os.path.join(work, "victim")
    os.makedirs(dir_a, exist_ok=True)
    os.makedirs(dir_b, exist_ok=True)
    report = {"args": vars(a), "checks": {}, "ok": False}
    try:
        pa = run(train_cmd(a, dir_a, mesh=a.mesh_a), "A:ref")
        pb = run(train_cmd(a, dir_b, mesh=a.mesh_b, kill_at=a.kill_at),
                 "B:victim")
        checks = report["checks"]
        checks["ref_completed"] = pa.returncode == 0
        checks["victim_sigkilled"] = pb.returncode == -signal.SIGKILL
        latest = os.path.join(dir_b, LATEST_NAME)
        checks["victim_left_latest"] = os.path.exists(latest)
        if checks["victim_left_latest"]:
            meta_b, _ = load_manifest(latest)
            # checkpoint cadence is 1, so the last completed round is k:
            # the kill fires mid-round k, after round k-1's snapshot
            checks["latest_at_kill_round"] = meta_b["round"] == a.kill_at
        pc = run(train_cmd(a, dir_b, mesh=a.mesh_c, resume=True), "C:recover")
        checks["recovery_completed"] = pc.returncode == 0
        checks["resumed_from_kill_round"] = \
            f"resumed from {latest} at round {a.kill_at}" in pc.stdout
        if checks["ref_completed"] and checks["recovery_completed"]:
            checks.update(compare_finals(os.path.join(dir_a, FINAL_NAME),
                                         os.path.join(dir_b, FINAL_NAME)))
        report["ok"] = all(v for k, v in checks.items()
                           if k != "first_diff_leaf")
        for k, v in checks.items():
            print(f"  {k}: {v}")
        print("kill_recover:", "ok" if report["ok"] else "FAIL")
    finally:
        if a.workdir is None:
            shutil.rmtree(work, ignore_errors=True)
    if a.json:
        os.makedirs(os.path.dirname(a.json) or ".", exist_ok=True)
        with open(a.json, "w") as f:
            json.dump(report, f, indent=1)
        print("wrote", a.json)
    sys.exit(0 if report["ok"] else 1)


if __name__ == "__main__":
    main()
