"""scheduler: median over the run's requests of admit time - due time, as
the OnlineReport has it (host clock inside the program, read as a span)."""

META = {"layer": "scheduler", "unit": "ms", "moves": "ttft_p95_ms",
        "source": "program_span"}


def compute(record):
    report = record.get("report")
    if not report:
        return None
    return report["queue_wait_p50_s"] * 1e3
