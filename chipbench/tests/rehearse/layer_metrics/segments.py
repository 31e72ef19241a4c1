"""A metric that exists only in the rehearsal's manifest: shows that a
per-layer metric is added by one file and one manifest entry."""

META = {"layer": "engine", "unit": "segments", "moves": "ttft_p95_ms",
        "source": "program_counter"}


def compute(record):
    report = record.get("report")
    return report["segments"] if report else None
