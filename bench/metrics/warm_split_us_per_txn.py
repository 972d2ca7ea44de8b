"""Host time in the ``warm-split`` spans per committed txn, in
microseconds: the warm protocol's own work in ``Cluster._run_warm``, the
split of a warm txn into its cold and hot sub-txns and the merge of
their results.  Silent where the program records no such span."""


def read(rec):
    s = rec["spans_s"].get("warm-split")
    if s is None or not rec["committed"]:
        return None
    return s * 1e6 / rec["committed"]
