"""Published peaks of one chip, keyed by the ``device_kind`` jax reports.

Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s in bf16, 16 GB
of HBM at 819 GB/s. A device that is not in the table is an error, never a
default: a share of another chip's peak is a wrong number. (Copied from
``paddle_tpu/observability/perf.py CHIP_PEAKS`` so that no later PR can move
the yardstick.)
"""

PEAKS = {
    "TPU v5 lite": {"bf16_flops_s": 197e12, "hbm_bytes_s": 819e9,
                    "hbm_bytes": 16 * 2**30},
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise SystemExit(
            f"chipbench: no published peaks for device kind {device_kind!r} "
            f"(known: {sorted(PEAKS)}); nothing was run") from None
