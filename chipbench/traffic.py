"""The one general traffic generator: it reads a traffic mix's file and
drives the served path, closed loop or open loop, over a window.

A mix (``chipbench/traffic/<name>.json``) holds parameters only:

``mode``          ``closed``: one caller sends the next micro-batch through
                  ``Engine.search`` when the last one returns, cycling the
                  queries in an order drawn from the seed, until the window
                  has passed; the last batch is finished and counted, and
                  the rate is taken over all the time it took.
                  ``open``: Poisson arrivals into ``AsyncEngine.submit`` at
                  ``rate_per_s``, each request timed from when it was due.
``k``             neighbours per query.
``groups``        list of ``{"query_params": {...}, "share": s}``: each
                  request's query parameters (closed: exactly one group).
``rate_per_s``    open loop: the fixed offered rate.
``deadline_ms``   open loop: each request's deadline (none when absent).
``drain_s``       open loop: how long after the window to wait for answers.

Every seed gets the same work: the open loop's gaps are the same multiset
of exponential quantiles in an order drawn from the seed, and groups get
their exact shares; only which query rides where changes.
"""

from __future__ import annotations

import queue
import threading
import time
from contextlib import nullcontext

import numpy as np


def span(name: str, on: bool):
    """A host span in the profiler's trace when tracing, else nothing."""
    if not on:
        return nullcontext()
    import jax

    return jax.profiler.TraceAnnotation(name)


def group_sequence(groups: list, count: int, rng) -> np.ndarray:
    """[count] group indices with each group's exact share, in an order
    drawn from ``rng``."""
    shares = np.array([float(g.get("share", 1.0)) for g in groups])
    sizes = np.floor(shares / shares.sum() * count).astype(np.int64)
    sizes[: count - sizes.sum()] += 1
    seq = np.repeat(np.arange(len(groups)), sizes)
    return seq[rng.permutation(count)]


def poisson_schedule(rate: float, seconds: float, rng) -> np.ndarray:
    """Due times (s from the window's start) of ``rate * seconds`` Poisson
    arrivals: the gaps are the exponential distribution's quantiles at
    (i + 1/2) / N, scaled to fill the window exactly, in an order drawn
    from ``rng``.  The first request is due at 0."""
    count = max(1, int(round(rate * seconds)))
    u = (np.arange(count) + 0.5) / count
    gaps = -np.log1p(-u)
    gaps = gaps[rng.permutation(count)]
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    return due * (seconds / (due[-1] + gaps[-1]))


def closed_loop(eng, Q: np.ndarray, mix: dict, seconds: float, rng,
                trace: bool) -> dict:
    """One caller, ``Engine.search`` on full micro-batches until the window
    has passed.  Returns the answers, counts and per-batch host spans."""
    (group,) = mix["groups"]
    params = dict(group.get("query_params", {}))
    b = eng.batch_size
    order = rng.permutation(len(Q))
    qidx, ids, dists, spans = [], [], [], []
    pos = 0
    failed = 0
    t0 = time.perf_counter()
    while True:
        take = order[np.arange(pos, pos + b) % len(order)]
        pos = (pos + b) % len(order)
        s = time.perf_counter()
        try:
            with span("cb.search", trace):
                d, i = eng.search(Q[take], **params)
        except Exception:                           # noqa: BLE001
            failed += b
        else:
            qidx.append(take)
            ids.append(i)
            dists.append(d)
        e = time.perf_counter()
        spans.append((s - t0, e - t0))
        if e - t0 >= seconds:
            break
    elapsed = time.perf_counter() - t0
    answered = sum(len(x) for x in qidx)
    k = int(mix["k"])
    return {
        "elapsed_s": elapsed, "attempted": answered + failed,
        "failed": failed, "batches": len(spans), "spans": spans,
        "answers": {
            "qidx": np.concatenate(qidx) if qidx else np.zeros(0, np.int64),
            "ids": np.concatenate(ids) if ids else np.zeros((0, k), np.int64),
            "dists": (np.concatenate(dists) if dists
                      else np.zeros((0, k), np.float32)),
            "group": np.zeros(answered, np.int64),
        },
        "per_group": {0: answered},
    }


def open_loop(srv, Q: np.ndarray, mix: dict, seconds: float, rng,
              trace: bool) -> dict:
    """Poisson arrivals into ``AsyncEngine.submit``.  One waiter thread per
    group takes that group's tickets in submission order, which is the
    order the pump answers a group in, so a ticket is read as soon as it
    is answered; latency runs from the due time to that reading."""
    groups = mix["groups"]
    due = poisson_schedule(float(mix["rate_per_s"]), seconds, rng)
    gseq = group_sequence(groups, len(due), rng)
    qseq = rng.choice(len(Q), size=len(due), replace=len(due) > len(Q))
    deadline = mix.get("deadline_ms")
    drain = float(mix.get("drain_s", 60.0))
    k = int(mix["k"])
    n = len(due)
    done_at = np.full(n, np.nan)
    ids = np.full((n, k), -1, np.int64)
    dists = np.full((n, k), np.inf, np.float32)
    late = np.zeros(n)
    inbox = [queue.Queue() for _ in groups]
    t0 = [0.0]

    def waiter(g: int) -> None:
        while True:
            item = inbox[g].get()
            if item is None:
                return
            r, ticket = item
            left = t0[0] + seconds + drain - time.perf_counter()
            try:
                with span(f"cb.wait.{g}", trace):
                    d, i = ticket.result(timeout=max(left, 0.001))
            except Exception:                       # noqa: BLE001
                continue
            done_at[r] = time.perf_counter() - t0[0]
            ids[r] = i
            dists[r] = d

    threads = [threading.Thread(target=waiter, args=(g,), daemon=True,
                                name=f"cb-waiter-{g}")
               for g in range(len(groups))]
    for t in threads:
        t.start()
    params = [dict(g.get("query_params", {})) for g in groups]
    rejected = 0
    t0[0] = time.perf_counter()
    try:
        for r in range(n):
            wait = t0[0] + due[r] - time.perf_counter()
            if wait > 0:
                with span("cb.sleep", trace):
                    time.sleep(wait)
            late[r] = time.perf_counter() - t0[0] - due[r]
            g = int(gseq[r])
            try:
                with span("cb.submit", trace):
                    ticket = srv.submit(Q[qseq[r]], deadline_ms=deadline,
                                        **params[g])
            except Exception:                       # noqa: BLE001
                rejected += 1
                continue
            inbox[g].put((r, ticket))
    finally:
        for box in inbox:
            box.put(None)
        for t in threads:
            t.join(seconds + drain + 5.0)
    elapsed = time.perf_counter() - t0[0]
    ok = np.isfinite(done_at)
    lat_ms = (done_at[ok] - due[ok]) * 1e3
    return {
        "elapsed_s": elapsed, "window_s": seconds, "attempted": n,
        "failed": int(n - ok.sum()), "rejected": rejected,
        "latency_ms": lat_ms,
        "lateness_ms": late * 1e3, "due_s": due,
        "answers": {"qidx": qseq[ok], "ids": ids[ok], "dists": dists[ok],
                    "group": gseq[ok]},
        "per_group": {g: int(((gseq == g) & ok).sum())
                      for g in range(len(groups))},
    }
