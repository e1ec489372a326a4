"""The 99th percentile, in ms, of the program's ``repro.pump.queue``
span over the open loop's served requests: from ``submit()`` until the
pump takes the request into a micro-batch.  The window's spans are the
last ``served`` recorded (the program records spans only while the
profile runs, which is the window); moves ``p99_ms``."""

from chipbench import bench

NAME = "repro.pump.queue"


def read(record, trace):
    try:
        from repro.serve import tracing
    except ImportError:
        return None
    n = (record.get("serve_counters") or {}).get("served")
    ms = [(e - s) / 1e6 for name, s, e, _ in tracing.recorded()
          if name == NAME]
    if not n or len(ms) < n or tracing.dropped():
        return None
    return bench.percentile(ms[-n:], 99)
