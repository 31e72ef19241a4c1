"""engine: of a first token's wait, the mean of the end of the admitting
step -> the return of that segment's fetch: the token exists on the device
and waits to be seen (the rest of ``ttft_admit_wait_ms``'s span)."""

from chipbench.layer_metrics.ttft_ingest_wait_ms import part_ms

META = {"layer": "engine", "unit": "ms", "moves": "ttft_p95_ms",
        "source": "program_counter"}


def compute(record):
    return part_ms(record, "delivery_wait_s")
