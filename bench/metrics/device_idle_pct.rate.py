"""Share of the window in which no operation ran on the device, in
percent, from the profiler trace, averaged over the chips."""


def read(rec):
    dev = rec["device"]
    if dev is None or not dev["window_s"]:
        return None
    return 100.0 * (1.0 - dev["busy_s"] / dev["window_s"])
