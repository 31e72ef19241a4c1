"""engine: ``segment_gap_ms`` again, for the cells that are judged on tokens
per second: the mean host gap a segment over the whole serve, from the
program's own ``segment_phases["gap"]`` (PR 38), seconds / count. A
program without the tally (before PR 38) reports nothing."""

from chipbench.layer_metrics.segment_gap_ms import compute  # noqa: F401

META = {"layer": "engine", "unit": "ms", "moves": "serve_tokens_per_s",
        "source": "program_span"}
