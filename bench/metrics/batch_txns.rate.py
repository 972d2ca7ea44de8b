"""Mean txns admitted per ``run_batch`` call in the window."""


def read(rec):
    if not rec["batches"]:
        return None
    return rec["admitted"] / rec["batches"]
