"""The switch engines' share of their roofline, in percent: the least
time the window's switch work needs (its bytes, ``roofline.work_bytes``,
over the chip's peak HBM bandwidth) over the engine programs' device time
in the trace, transfers excluded.  Silent when no engine program ran."""


def read(rec):
    dev = rec["device"]
    if dev is None or not dev["engine_s"] or not rec["work_bytes"]:
        return None
    least = rec["work_bytes"] / rec["hbm_bytes_per_s"]
    return 100.0 * least / dev["engine_s"]
