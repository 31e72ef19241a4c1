"""kernels: the ``mla_paged_attention`` kernel's share of its roofline in
the traced slice, all layers. A decode tick's query reads each cached row of
its slot once: 278,528 operations (128 heads x 2 x (576 + 512)) on 1,152
bytes, 242 to the byte against the chip's 240.5, so the least time of a row
is the LARGER of the two. An admission's rows are read once for all its
queries: its causal (query, row) pairs' operations against its rows' bytes
(true prompt lengths, mean over the run's requests). Over the kernel's
device time."""

from chipbench import flops_latent_moe as flops
from chipbench.layer_metrics.grouped_expert_matmul_roofline import \
    kernel_seconds

META = {"layer": "kernels", "unit": "%", "moves": "serve_tokens_per_s",
        "source": "device_trace"}


def compute(record):
    sl, chip = record.get("slice"), record.get("chip")
    secs = kernel_seconds(record, "mla_paged_attention")
    if record.get("kind") != "serve_latent_moe" or not sl or not chip \
            or not secs:
        return None
    per = record["report"]["per_request"]
    pairs = sum(r["prompt_len"] * (r["prompt_len"] + 1) / 2.0
                for r in per) / len(per)
    rows = sum(r["prompt_len"] for r in per) / len(per)
    decode = sl["steps"] - sl["admits"]
    least = flops.attention_floor_s(
        record["config"], chip, decode * record["kv_rows_per_decode_step"],
        sl["admits"] * pairs, sl["admits"] * rows)
    return least / secs * 100.0
