"""Share of the closed-loop window in which no operation ran on the
device, in %: 100 * (1 - busy / window) from the trace; moves ``qps``."""


def read(record, trace):
    if record["traffic"]["mode"] != "closed":
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
