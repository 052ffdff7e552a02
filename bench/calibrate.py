"""Readings the correctness limits are set from, for one cell, in one
process (set-up is long, so one process serves every seed):

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,... \\
        [--variants 3]

For each seed it builds the cell's server as a run does, runs the first
rounds the reference follows, and prints the program's numbers (the lower
readings).  For the first ``--variants`` seeds it also prints the numbers
of what stands in the program's place, each planted in the reference at
the program's trajectory:

* ``control`` — the reference with every matmul operand in float8 e4m3,
  the nearest precision below the configuration's bfloat16, for the
  clients' scalars and for the mask's scores;
* ``half_batch`` — half of each batch left out, the mean over the rest;
* ``half_clients`` — the uploads of half of the clients left out of the
  server's mean.

A state left unchanged reads 1 on both norm gaps by their definition and
needs no run; an altered upload is the test's (``tests/test_harness.py``).
The benchmark's own runs never run this.  One JSON line per reading.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

import run as R
from reference import (Reference, compare, g_norm_gap, leaf_norm_gap,
                       top_indices, topk_gap)
from spec import BENCH, CACHE_DIR, ROOT, load_cell


def readings(cell, seed: int, variants: bool, emit) -> None:
    import fedrun
    t0 = time.time()
    sess = fedrun.Session(cell, seed)
    sess.build()
    rec = sess.first_rounds()
    sess.close()
    ref = Reference(cell.family, cell.published, cell.config["dtype"],
                    cell.traffic, fl_seed=sess.seeds["fl"],
                    weights_seed=sess.seeds["weights"])
    W = ref.weights()
    t1 = time.time()
    g_ref = ref.client_gs(rec, weights=W)
    counts = [len(i) for i in rec.idx]
    group = ref.mask_group(ref.leaf_sizes(), counts)
    scores = ref.mask_scores(rec, group, weights=W)
    t_ref = time.time() - t1
    emit(dict(seed=seed, who="program",
              **compare(ref, rec, g_ref=g_ref, mask_scores=scores),
              g_prog=[np.asarray(g).tolist() for g in rec.gs],
              g_ref=g_ref.tolist(), mask_group=group, mask_counts=counts,
              setup_s=t1 - t0,
              reference_s=t_ref, spans=sess.spans))
    if not variants:
        return
    g_ctrl = ref.client_gs(rec, fp8=True, weights=W)
    m = int(sum(len(rec.idx[i]) for i in group))
    ctrl_mask = top_indices(ref.mask_scores(rec, group, fp8=True, weights=W),
                            m)
    emit(dict(seed=seed, who="control",
              g_norm_gap=g_norm_gap(g_ctrl, g_ref),
              mask_gap=topk_gap(scores, ctrl_mask), g=g_ctrl.tolist()))
    del scores, ctrl_mask
    g_half = ref.client_gs(rec, rows=cell.traffic["batch_size"] // 2,
                           weights=W)
    emit(dict(seed=seed, who="half_batch",
              g_norm_gap=g_norm_gap(g_half, g_ref),
              g=g_half.tolist()))
    sizes = [len(i) for i in rec.idx]
    K = np.asarray(rec.gs[0]).shape[0]
    full = ref.replay(rec, len(rec.gs))
    half = ref.replay(rec, len(rec.gs), clients=range(K // 2))
    emit(dict(seed=seed, who="half_clients",
              update_norm_gap=leaf_norm_gap(rec.p[0], np.asarray(half[1]),
                                            np.asarray(full[1]), sizes),
              change_norm_gap=leaf_norm_gap(rec.p[0], np.asarray(half[-1]),
                                            np.asarray(full[-1]), sizes)))


def main(argv=None, *, root: str = ROOT, bench: str = BENCH,
         require_tpu: bool = True, cache_dir: str = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated whole numbers")
    ap.add_argument("--variants", type=int, default=3,
                    help="seeds (the first ones) that also read the "
                         "control and the planted faults")
    a = ap.parse_args(argv)
    cell = load_cell(a.workload, root, bench)
    import os
    if R.start(cell, cache_dir or os.path.join(ROOT, CACHE_DIR),
               require_tpu) is None:
        return 2

    def emit(d):
        print(json.dumps(d), flush=True)

    for i, s in enumerate(int(x) for x in a.seeds.split(",")):
        readings(cell, s, i < a.variants, emit)
    return 0


if __name__ == "__main__":
    sys.exit(main())
