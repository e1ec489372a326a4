"""Share of the open-loop window in which no operation ran on the
device, in %: 100 * (1 - busy / window) from the trace; moves ``p99_ms``."""


def read(record, trace):
    if record["traffic"]["mode"] != "open":
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
