"""Host time in the ``packet-build`` spans (``core/packets.py``
``build_packets`` and staging) per committed txn, in microseconds."""


def read(rec):
    s = rec["spans_s"].get("packet-build")
    if s is None or not rec["committed"]:
        return None
    return s * 1e6 / rec["committed"]
