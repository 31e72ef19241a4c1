"""model step: ``experts_ms_per_step`` again, for the window / full cell
(ticks of 4 rows an expert and admissions of ~256 in one slice)."""

from chipbench.layer_metrics.experts_ms_per_step import compute  # noqa: F401

META = {"layer": "model step", "unit": "ms", "moves": "serve_tokens_per_s",
        "source": "device_trace"}
