"""Host time in the ``cold`` spans per committed txn, in microseconds: the
NO_WAIT 2PL and 2PC of cold txns, retries and aborts included, and the
cold parts of warm txns."""


def read(rec):
    s = rec["spans_s"].get("cold")
    if s is None or not rec["committed"]:
        return None
    return s * 1e6 / rec["committed"]
