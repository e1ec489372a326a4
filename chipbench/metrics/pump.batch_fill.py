"""Share of the pump's micro-batch rows that carried a request:
``served / (batches * batch_size)`` from the ``ServeMetrics`` counters of
the window, in %; moves ``p99_ms`` in the open loop."""


def read(record, trace):
    c = record.get("serve_counters") or {}
    if not c.get("batches"):
        return None
    return 100.0 * c["served"] / (c["batches"] * record["batch_size"])
