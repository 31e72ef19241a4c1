"""engine: ``tokens_per_tick`` again, for the window / full cell (64 slots:
at best 64 tokens a decode tick, one an admission)."""

from chipbench.layer_metrics.tokens_per_tick import compute  # noqa: F401

META = {"layer": "engine", "unit": "tokens", "moves": "serve_tokens_per_s",
        "source": "program_counter"}
