"""Host step: median over the window's steps of the host's own time in
a step, the summed self time of every engine phase span except
`repro.wait_tokens` (`bench.spans`)."""
import statistics


def read(rec: dict):
    steps = (rec.get("spans") or {}).get("host_step_ms")
    if not steps:
        return None
    return statistics.median(steps)
