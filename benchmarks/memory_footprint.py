"""Paper §1/§2.3 memory claim: ZO fine-tuning needs no activation storage.

Compares `compiled.memory_analysis()` of the production MEERKAT `zo_fl`
step against the first-order (backprop) step for the same architecture,
input shape and mesh — the dry-run machinery gives exact per-device
numbers.  The backward pass must keep every layer's activations live
(or pay remat recompute); the ZO dual forward keeps one layer period.

The measurement runs in a subprocess because it needs the 512 forced host
devices before jax initializes (benchmarks.run imports jax early).  The
child is a host-device dry run: it pins ``JAX_PLATFORMS=cpu``, so it never
waits on an accelerator its parent holds.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

_CHILD = r"""
import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
import json
from repro.launch.dryrun import build_lowerable
from repro.configs import get_config
from repro.configs.base import InputShape
from repro.launch.mesh import make_mesh_from_config, mesh_config

cfg = get_config("qwen3-4b")
shape = InputShape("train_4k", seq_len=4096, global_batch=256, kind="train")
mc = mesh_config()
mesh = make_mesh_from_config(mc)
out = {}
for step in ["zo_fl", "first_order"]:
    jf, args = build_lowerable(cfg, shape, mesh, mc, step)
    ma = jf.lower(*args).compile().memory_analysis()
    out[step] = dict(
        argument_bytes=int(ma.argument_size_in_bytes),
        temp_bytes=int(ma.temp_size_in_bytes),
        peak_est_bytes=int(ma.argument_size_in_bytes
                           + ma.output_size_in_bytes
                           + ma.temp_size_in_bytes - ma.alias_size_in_bytes))
print("RESULT " + json.dumps(out))
"""


def run(quick: bool = True, seed: int = 0) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = "src" + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", _CHILD], env=env,
                          capture_output=True, text=True, timeout=1800)
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT ")]
    if not line:
        raise RuntimeError(f"child failed:\n{proc.stderr[-2000:]}")
    out = json.loads(line[0][len("RESULT "):])
    for step, m in out.items():
        print(f"  {step:12s} temp={m['temp_bytes'] / 1e9:7.2f} GB  "
              f"peak~{m['peak_est_bytes'] / 1e9:7.2f} GB /device")
    ratio = out["first_order"]["temp_bytes"] / max(
        1, out["zo_fl"]["temp_bytes"])
    print(f"  first-order temp / ZO temp = {ratio:.1f}x")
    return {"table": "memory_footprint", "arch": "qwen3-4b",
            "per_device": out, "temp_ratio": ratio,
            "claim_zo_saves_activation_memory": bool(ratio > 1.5)}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    a = ap.parse_args()
    res = run(quick=not a.full, seed=a.seed)
    from benchmarks import common as C
    print("saved:", C.save_result("memory_footprint", res))


if __name__ == "__main__":
    main()
