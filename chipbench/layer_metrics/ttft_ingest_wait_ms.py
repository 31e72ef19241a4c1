"""scheduler: of a first token's wait, the mean over the run's requests of
due time -> the top of the serve loop that ingested the request (the loop
was inside a running segment, or the bounded queue was full). One of the
four parts ``OnlineReport.ttft_parts_mean_s`` splits the first-token time
into (``scheduler._ttft_parts``: stamps the loop takes anyway); the four
means sum to the run's mean first-token time on the program's clock. A
program without the split (before PR 25) reports nothing."""

META = {"layer": "scheduler", "unit": "ms", "moves": "ttft_p95_ms",
        "source": "program_span"}


def part_ms(record, key):
    """Mean seconds of one part of the split, in ms; None where the
    report has no split."""
    parts = (record.get("report") or {}).get("ttft_parts_mean_s")
    if not parts or parts.get(key) is None:
        return None
    return parts[key] * 1e3


def compute(record):
    return part_ms(record, "ingest_s")
