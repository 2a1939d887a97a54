"""Packer: share of launched token rows that carried no useful work.

1 - (prompt tokens computed + decode rows of live sequences) / token rows
launched, over the window; both from the program's counters
(`Engine.prefilled_tokens`, `Engine.launched_token_slots`) and the
window's decode token events.
"""


def read(rec: dict):
    launched = rec["counters"]["launched_token_slots"]
    if launched <= 0:
        return None
    useful = rec["counters"]["prefilled_tokens"] + len(rec["decode_ctx"])
    return 100.0 * (1.0 - useful / launched)
