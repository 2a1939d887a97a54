"""Host step: share of the traced window in which chip 0 is idle while
the engine's thread is inside one of its phase spans other than
`repro.wait_tokens`: device idle that host work of the engine holds
(`bench.spans`)."""
from bench import spans as S


def read(rec: dict):
    sp = rec.get("spans")
    if not sp or sp["window_s"] <= 0:
        return None
    held = sum(s for label, s in sp["idle_by_span"].items()
               if label.startswith(S.ENGINE_PREFIX) and label != S.WAIT)
    return 100.0 * held / sp["window_s"]
