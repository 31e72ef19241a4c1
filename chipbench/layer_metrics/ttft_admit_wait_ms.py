"""engine: of a first token's wait, the mean of the admitting segment's
dispatch -> the end of the step of its loop that admitted the request
(earlier admissions and steps, then the request's own prefill; the token
then exists on the device). The segment's dispatch -> fetch span split at
the admission's index in the event log, equal steps assumed (see
``ttft_ingest_wait_ms``)."""

from chipbench.layer_metrics.ttft_ingest_wait_ms import part_ms

META = {"layer": "engine", "unit": "ms", "moves": "ttft_p95_ms",
        "source": "program_counter"}


def compute(record):
    return part_ms(record, "admit_wait_s")
