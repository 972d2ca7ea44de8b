"""Host time in ``Cluster._classify_batch`` (the ``classify`` spans) per
committed txn, in microseconds."""


def read(rec):
    s = rec["spans_s"].get("classify")
    if s is None or not rec["committed"]:
        return None
    return s * 1e6 / rec["committed"]
