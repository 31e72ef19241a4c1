"""engine: ``experts_hit_per_step`` again, for the window / full cell (of 64
= 4 layers x 16 held experts; an admission hits all of them)."""

from chipbench.layer_metrics.experts_hit_per_step import \
    compute  # noqa: F401

META = {"layer": "engine", "unit": "experts", "moves": "serve_tokens_per_s",
        "source": "program_counter"}
