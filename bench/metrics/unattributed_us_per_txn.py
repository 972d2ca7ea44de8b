"""Host time inside the window's ``run_batch`` + ``drain`` calls that no
span covers, per committed txn, in microseconds: the calls' time less
every span the program recorded in them."""


def read(rec):
    if not rec["committed"] or not rec["spans_s"]:
        return None
    spanned = sum(rec["spans_s"].values())
    return (rec["call_s"] - spanned) * 1e6 / rec["committed"]
