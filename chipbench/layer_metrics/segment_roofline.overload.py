"""kernels, whole program (serve): ``segment_roofline`` again, for the cells that
are judged on tokens per second (a per-layer metric names the ONE end-to-end
metric it moves, and that one has to be reported in every cell it is in)."""

from chipbench.layer_metrics.segment_roofline import compute  # noqa: F401

META = {"layer": "kernels", "unit": "%", "moves": "serve_tokens_per_s",
        "source": "device_trace"}
