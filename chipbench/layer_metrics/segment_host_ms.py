"""engine: what one segment costs the host besides waiting for the device,
from the program's own phase spans (``OnlineReport.segment_phases``): the
seconds of ``ingest`` + ``pick`` + ``inputs`` + ``launch`` + ``replay`` +
``telemetry`` over the serve / its segments (``fetch``, the wait for the
device, is left out). A program without the spans (before PR 25) reports
nothing."""

META = {"layer": "engine", "unit": "ms", "moves": "serve_tokens_per_s",
        "source": "program_span"}

HOST_PHASES = ("ingest", "pick", "inputs", "launch", "replay", "telemetry")


def compute(record):
    report = record.get("report") or {}
    phases = report.get("segment_phases")
    if not phases or not report.get("segments"):
        return None
    return sum(phases[p]["seconds"] for p in HOST_PHASES
               if p in phases) / report["segments"] * 1e3
