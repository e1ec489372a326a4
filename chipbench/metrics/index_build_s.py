"""Seconds of ``Engine.build`` (corpus to index on the device, IVF's
k-means included), host clock; moves ``setup_s``."""


def read(record, trace):
    return record["setup"]["build_s"]
