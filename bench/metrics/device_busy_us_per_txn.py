"""Device time in which an operation ran (the union of op intervals in the
profiler trace, summed over the chips) per committed txn, in
microseconds."""


def read(rec):
    dev = rec["device"]
    if dev is None or not rec["committed"]:
        return None
    return dev["busy_total_s"] * 1e6 / rec["committed"]
