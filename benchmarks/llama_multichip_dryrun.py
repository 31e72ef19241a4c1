"""BASELINE config 4: LLaMA hybrid-parallel step (TP + ZeRO-3) — dry run.

Multi-chip hardware isn't present in this environment; this script compiles
and executes the FULL hybrid train step on the virtual 8-device CPU mesh
(the same program the driver validates via __graft_entry__.dryrun_multichip)
and reports compile+step wall time. Run with:

  env JAX_PLATFORMS=cpu \
      XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python benchmarks/llama_multichip_dryrun.py
"""

import json
import os
import sys

# runnable standalone: the repo root (one level up) holds paddle_tpu
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import time


def main():
    import __graft_entry__ as g

    t0 = time.perf_counter()
    # call the in-process impl: this script's documented env already provides
    # the 8-device CPU platform, and timing must exclude subprocess startup
    g._dryrun_impl(8)
    dt = time.perf_counter() - t0
    print(json.dumps({
        "metric": "llama_hybrid_dryrun_wall", "value": round(dt, 2),
        "unit": "seconds", "vs_baseline": 1.0,
    }))


if __name__ == "__main__":
    sys.exit(main())
