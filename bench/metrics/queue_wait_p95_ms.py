"""Scheduler: 95th percentile of the admission wait of the requests due
in the window (the TTFT sample), from the stamps the scheduler sets on
each request (`bench.spans.queue_wait_ms`); one still waiting at the
close counts its wait so far."""
import numpy as np


def read(rec: dict):
    waits = rec.get("queue_wait_ms")
    if not waits:
        return None
    return float(np.percentile(np.asarray(waits, float), 95))
