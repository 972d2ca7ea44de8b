"""Host time inside the window's ``run_batch`` + ``drain`` calls that no
span covers (the cold 2PL/2PC path, WAL appends, result scatter) per
committed txn, in microseconds."""

SPANS = ("classify", "packet-build", "dispatch", "drain")


def read(rec):
    if not rec["committed"] or not rec["spans_s"]:
        return None
    spanned = sum(rec["spans_s"].get(s, 0.0) for s in SPANS)
    return (rec["call_s"] - spanned) * 1e6 / rec["committed"]
