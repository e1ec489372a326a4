"""The brute-force scan's share of its roofline, in %: the least time the
window's micro-batches need (each reads the corpus once and makes
2 * b * n * d operations; ``work.scan_need_s``), over the device-busy
time of the window, which is the whole search program's; moves ``qps``."""

from chipbench import work


def read(record, trace):
    if record["config"]["index"]["algo"] != "BruteForce":
        return None
    return 100.0 * work.scan_need_s(record) / trace["busy_s"]
