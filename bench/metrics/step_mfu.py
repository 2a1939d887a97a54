"""Model step: useful model FLOPs of the window over (window x peak).

Per computed token 2 x the per-layer matmul parameters plus attention at
its own context; the LM head once per sampled row (`bench.work`).
Padding, dead rows and logits of rows that sample nothing earn nothing.
"""
from bench import work


def read(rec: dict):
    if rec["window_s"] <= 0 or not rec["peaks"]:
        return None
    flops = work.model_flops(rec["dims"], rec["prompts"], rec["decode_ctx"],
                             rec["sampled_rows"])
    if flops <= 0:
        return None
    return 100.0 * flops / (rec["window_s"] * rec["peaks"]["bf16_flops_per_s"])
