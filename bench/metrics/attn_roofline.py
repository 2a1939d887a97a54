"""Attention kernels: least time of the attention work the traffic needed
over the device time of the Pallas attention kernels in the trace.

The least time is max(FLOPs / peak, bytes / bandwidth), counted apart for
decode rows and for prompt rows, from the window's token events at the
configuration's own head size (`bench.work`).  Only the kernels' own
events count: copies that pad their inputs are other ops.
"""
from bench import work

KERNELS = ("paged_decode", "paged_decode_segmented", "paged_segment_reduce",
           "paged_prefill_qblock")


def is_kernel(op: str) -> bool:
    return any(k in op for k in KERNELS)


def read(rec: dict):
    tr = rec.get("trace")
    if not tr or not rec["peaks"]:
        return None
    kernel_s = sum(t for op, t in tr["ops_s"].items() if is_kernel(op))
    if kernel_s <= 0:
        return None
    m, peaks = rec["dims"], rec["peaks"]
    least = work.least_seconds(*work.decode_attn_work(m, rec["decode_ctx"]),
                               peaks) \
        + work.least_seconds(*work.chunk_attn_work(m, rec["prompts"]), peaks)
    return 100.0 * least / kernel_s
