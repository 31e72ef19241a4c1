"""engine: the mean host gap a segment, over the whole serve: the
program's own ``segment_phases["gap"]`` (PR 38), from the return of a
segment's ``fetch`` to the return of the next one's ``launch`` — the host
time in which the engine has nothing in flight, so the device waits —
seconds / count. A serve's first segment and one after the loop waited
for work have no gap. A program without the tally (before PR 38)
reports nothing."""

META = {"layer": "engine", "unit": "ms", "moves": "tpot_mean_ms",
        "source": "program_span"}


def compute(record):
    report = record.get("report") or {}
    gap = (report.get("segment_phases") or {}).get("gap")
    if not gap or not gap["count"]:
        return None
    return gap["seconds"] / gap["count"] * 1e3
