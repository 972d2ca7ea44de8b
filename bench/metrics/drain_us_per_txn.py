"""Host time in the ``drain`` spans (``PendingBatch.results_np``: the wait
for the device and the result copy to the host) per committed txn, in
microseconds."""


def read(rec):
    s = rec["spans_s"].get("drain")
    if s is None or not rec["committed"]:
        return None
    return s * 1e6 / rec["committed"]
