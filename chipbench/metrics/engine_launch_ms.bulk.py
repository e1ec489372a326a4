"""Host milliseconds per closed-loop micro-batch in the program's
``repro.engine.launch`` span: the query block copied to the device and
the compiled search dispatched.  The mean over the window's spans, the
last ``device_batches`` recorded (the program records spans only while
the profile runs, which is the window); moves ``qps``."""

NAME = "repro.engine.launch"


def read(record, trace):
    try:
        from repro.serve import tracing
    except ImportError:
        return None
    n = record["device_batches"]
    ms = [(e - s) / 1e6 for name, s, e, _ in tracing.recorded()
          if name == NAME]
    if record["traffic"]["mode"] != "closed" or not n or len(ms) < n \
            or tracing.dropped():
        return None
    return sum(ms[-n:]) / n
