"""model step (serve): ``tick_device_ms`` again, for the cells that
are judged on tokens per second (a per-layer metric names the ONE end-to-end
metric it moves, and that one has to be reported in every cell it is in)."""

from chipbench.layer_metrics.tick_device_ms import compute  # noqa: F401

META = {"layer": "model step", "unit": "ms", "moves": "serve_tokens_per_s",
        "source": "device_trace"}
