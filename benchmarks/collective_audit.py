"""Print the per-axis collective inventory of the baseline-ladder steps.

Runs on the 8-device virtual CPU mesh (no TPU needed): compiles the SAME
programs ``tests/test_scaling_evidence.py`` pins (shared builders in
``hlo_audit``), runs the program auditor's collective/mesh pass over
their optimized HLO (r9: this script is a front-end to
``paddle_tpu.analysis.hlo.collective_check`` — the pass the budget gate
enforces), and prints the tables SCALING.md embeds. Usage::

    env JAX_PLATFORMS=cpu \
        XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python benchmarks/collective_audit.py
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import numpy as np


def _check(txt, mesh, tag):
    """The promoted pass: attribution must be clean (the same contract
    the gate's canonical programs carry)."""
    from paddle_tpu.analysis.hlo import collective_check

    chk = collective_check(txt, mesh)
    status = "clean" if chk.ok else (
        f"{len(chk.unattributed)} unattributed / "
        f"{len(chk.partial_ring)} partial-ring")
    print(f"[analysis.collective_check] {tag}: {status}, "
          f"{len(chk.inventory)} collectives, "
          f"{chk.total_bytes / 2**20:.2f} MiB")
    return chk


def main():
    from paddle_tpu.distributed.auto_parallel.hlo_audit import (
        build_dp_resnet_compiled,
        build_llama_hybrid_compiled,
        format_inventory,
    )
    from paddle_tpu.parallel import set_mesh

    hlo, mesh, model, _, _ = build_dp_resnet_compiled()
    chk = _check(hlo, mesh, "DP-8 ResNet18")
    grad_b = sum(4 * int(np.prod(p.shape)) for p in model.parameters()
                 if not p.stop_gradient)
    print("== DP-8 ResNet18 train step (b16, fp32 grads) ==")
    print(format_inventory(chk.inventory))
    print(f"trainable grad bytes: {grad_b / 2**20:.2f} MiB; "
          f"all-reduce payload: "
          f"{sum(e['bytes'] for e in chk.inventory) / 2**20:.2f} MiB")
    print()

    try:
        txt, mesh2 = build_llama_hybrid_compiled()
        chk2 = _check(txt, mesh2, "LLaMA-tiny hybrid")
        print("== LLaMA-tiny hybrid step (dp=2 x sharding=2 x mp=2, "
              "ZeRO-3 + TP) ==")
        print(format_inventory(chk2.inventory))
    finally:
        set_mesh(None)


if __name__ == "__main__":
    if len(jax.devices()) < 8:
        raise SystemExit("run with the 8-device virtual CPU mesh (see "
                         "module docstring)")
    main()
