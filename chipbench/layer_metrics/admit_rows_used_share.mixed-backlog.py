"""engine: ``admit_rows_used_share`` again, for the window / full cell
(``serving.window.admit_rows_used`` / ``.admit_rows``: what of an
admission's 4,096 positions is the prompt; the mixed queue's mean prompt is
2,176)."""

from chipbench.layer_metrics.admit_rows_used_share import \
    compute  # noqa: F401

META = {"layer": "engine", "unit": "%", "moves": "serve_tokens_per_s",
        "source": "program_counter"}
