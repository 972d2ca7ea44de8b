"""Host time in the ``dispatch`` spans (``SwitchEngine`` /
``ShardedSwitchEngine.execute_batch``) per committed txn, in
microseconds."""


def read(rec):
    s = rec["spans_s"].get("dispatch")
    if s is None or not rec["committed"]:
        return None
    return s * 1e6 / rec["committed"]
