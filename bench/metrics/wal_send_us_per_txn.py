"""Host time in the ``wal-send`` spans (the switch_send WAL appends logged
before each switch dispatch) per committed txn, in microseconds."""


def read(rec):
    s = rec["spans_s"].get("wal-send")
    if s is None or not rec["committed"]:
        return None
    return s * 1e6 / rec["committed"]
