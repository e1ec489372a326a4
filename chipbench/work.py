"""The work a search needs, from its shapes, and the chip's peaks.

The counts are what the algorithm has to do, not what today's kernels do,
so a fused, renamed or rewritten kernel is read against the same work:

* brute force scans the corpus once per micro-batch: ``n * d * 4`` bytes
  read, ``2 * b * n * d`` operations;
* IVF reads the centroids once per micro-batch (``lists * d * 4`` bytes,
  ``2 * b * lists * d`` operations) and, per query, the rows of its
  ``n_probes`` lists, ``n / lists`` rows each on average
  (``n_probes * n / lists * d * 4`` bytes, twice that many operations
  over ``d``).

Float32 operations are held against the bf16 peak, the chip's highest, so
the least time is a true lower bound; at these shapes bytes bound it.
"""

from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    """The peak table's row for a device kind; a kind missing from the
    table is an error, never a default."""
    table = json.loads(PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name}; known: {sorted(table)}")
    return table[device_kind]


def least_time_s(flops: float, nbytes: float, peak: dict) -> float:
    """The roofline: the larger of operations over peak rate and bytes
    over peak bandwidth."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])


def scan_work(n: int, d: int, b: int, itemsize: int = 4):
    """(operations, bytes) one brute-force micro-batch of b queries needs."""
    return 2.0 * b * n * d, float(n * d * itemsize)


def ivf_batch_work(lists: int, d: int, b: int, itemsize: int = 4):
    """(operations, bytes) of the coarse probe of one IVF micro-batch."""
    return 2.0 * b * lists * d, float(lists * d * itemsize)


def ivf_query_work(n: int, lists: int, d: int, n_probes: int,
                   itemsize: int = 4):
    """(operations, bytes) of one query's rerank over its probed lists."""
    rows = n_probes * n / lists
    return 2.0 * rows * d, rows * d * itemsize


def scan_need_s(record: dict) -> float:
    """Least seconds the window's brute-force micro-batches need."""
    c = record["config"]["corpus"]
    flops, nbytes = scan_work(c["n"], c["d"], record["batch_size"])
    return record["device_batches"] * least_time_s(
        flops, nbytes, peaks(record["device_kind"]))


def ivf_need_s(record: dict) -> float:
    """Least seconds the window's IVF micro-batches need: each batch's
    coarse probe and each answered query's probed lists."""
    cfg = record["config"]
    c = cfg["corpus"]
    lists = cfg["index"]["build"]["n_clusters"]
    flops, nbytes = ivf_batch_work(lists, c["d"], record["batch_size"])
    flops *= record["device_batches"]
    nbytes *= record["device_batches"]
    for g in record["groups"]:
        f, b = ivf_query_work(c["n"], lists, c["d"],
                              g["query_params"]["n_probes"])
        flops += f * g["answered"]
        nbytes += b * g["answered"]
    return least_time_s(flops, nbytes, peaks(record["device_kind"]))
