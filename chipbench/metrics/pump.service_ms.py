"""Milliseconds of the program's ``repro.pump.batch`` span, the mean over
the open loop's pump micro-batches: from the pump taking a batch until
every ticket of it is resolved.  The window's spans are the last
``batches`` recorded (the program records spans only while the profile
runs, which is the window); moves ``p99_ms``."""

NAME = "repro.pump.batch"


def read(record, trace):
    try:
        from repro.serve import tracing
    except ImportError:
        return None
    n = (record.get("serve_counters") or {}).get("batches")
    ms = [(e - s) / 1e6 for name, s, e, _ in tracing.recorded()
          if name == NAME]
    if not n or len(ms) < n or tracing.dropped():
        return None
    return sum(ms[-n:]) / n
