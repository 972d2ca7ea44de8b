"""Host time in the ``scatter`` spans (the result scatter after each drain:
the un-permute, the switch_result WAL appends and the client result
lists) per committed txn, in microseconds."""


def read(rec):
    s = rec["spans_s"].get("scatter")
    if s is None or not rec["committed"]:
        return None
    return s * 1e6 / rec["committed"]
