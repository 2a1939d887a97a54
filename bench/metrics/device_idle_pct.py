"""Device: share of the traced window in which no op ran on the TPU
(1 - union of op intervals / window, averaged over the chips)."""


def read(rec: dict):
    tr = rec.get("trace")
    if not tr or tr["window_s"] <= 0 or tr["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
