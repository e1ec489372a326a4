"""Host milliseconds per closed-loop micro-batch outside the device: the
harness's ``cb.search`` span around each ``Engine.search`` call less the
device-busy time inside it, averaged over the window's calls; moves
``qps``."""


def read(record, trace):
    spans = [s for s in trace["spans"] if s[0] == "cb.search"]
    if not spans:
        return None
    return 1e3 * sum(dur - busy for _, dur, busy in spans) / len(spans)
