"""Switch dispatches (``switch.dispatch_count`` over the window) per 1,000
committed txns."""


def read(rec):
    if not rec["committed"]:
        return None
    return rec["dispatches"] * 1000.0 / rec["committed"]
