"""The IVF search's share of its roofline in the open loop, in %: the
least time of each micro-batch's coarse probe and each answered query's
``n_probes`` lists of ``n / lists`` rows (``work.ivf_need_s``), over the
device-busy time of the window; moves ``p99_ms``."""

from chipbench import work


def read(record, trace):
    if record["config"]["index"]["algo"] != "IVF" \
            or record["traffic"]["mode"] != "open":
        return None
    return 100.0 * work.ivf_need_s(record) / trace["busy_s"]
