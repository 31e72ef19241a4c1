"""scheduler: of a first token's wait, the mean of ingest -> the dispatch
of the segment that admitted the request: seen by the scheduler, but no
slot, page or place in a pick yet (see ``ttft_ingest_wait_ms``)."""

from chipbench.layer_metrics.ttft_ingest_wait_ms import part_ms

META = {"layer": "scheduler", "unit": "ms", "moves": "ttft_p95_ms",
        "source": "program_span"}


def compute(record):
    return part_ms(record, "slot_wait_s")
