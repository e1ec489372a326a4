#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

In order: the persistent compilation cache is put in ``<checkout>/
.jax_cache``; the run fails (exit 2, no result) unless JAX finds a TPU
with as many chips as the cell asks for; the corpus and queries are made
on the device from ``--seed``; the index is built through
``Engine.build``; the cell's own shapes are warmed; the traffic runs for
``--seconds``; then, with the window closed, the peak memory is read, the
program's state is freed and every answer is judged against the plain
reference (``check.py``).

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each number compared with its limit.
Diagnostics go to standard error, the checks last.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/core/compile/jaxpr_trace_duration")


class NoAccelerator(RuntimeError):
    pass


def require_accelerator(chips: int) -> list:
    """The TPU devices, or ``NoAccelerator``: no fallback to the CPU."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoAccelerator(f"needs a TPU, JAX found {devices[0].platform!r}"
                            f" ({devices[0].device_kind})")
    if len(devices) < chips:
        raise NoAccelerator(f"the cell needs {chips} chips, JAX found "
                            f"{len(devices)}")
    return devices[:chips]


def parse_args(argv):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def log(**fields) -> None:
    print(json.dumps(fields, default=float), file=sys.stderr, flush=True)


class CompileCounter:
    """Counts traces and compiles while ``on``."""

    def __init__(self):
        import jax

        self.on = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self)

    def __call__(self, event, duration, **_):
        if self.on and event in COMPILE_EVENTS:
            self.count += 1

    def close(self):
        import jax

        jax.monitoring.unregister_event_duration_listener(self)


class GcPauses:
    """Python's garbage collections while ``on``: [generation, start s
    from ``t0``, seconds] each, so that a stall in the window can be told
    from a collection."""

    def __init__(self):
        self.on = False
        self.t0 = 0.0
        self.pauses = []
        self._start = None
        gc.callbacks.append(self)

    def __call__(self, phase, info):
        if phase == "start":
            self._start = time.perf_counter()
        elif self.on and self._start is not None:
            end = time.perf_counter()
            self.pauses.append([info["generation"], self._start - self.t0,
                                end - self._start])

    def close(self):
        gc.callbacks.remove(self)


def build_engine(config: dict, mix: dict, X):
    from repro.serve import Engine

    index = config["index"]
    return Engine.build(index["algo"], X, metric=config["corpus"]["metric"],
                        build_params=index.get("build", {}),
                        query_params=index.get("query", {}),
                        k=int(mix["k"]),
                        batch_size=int(config["engine"]["batch_size"]))


def warm(eng, Q, mix: dict, config: dict):
    """Run every shape and group the window will use; for an open loop,
    start the pump and send it one request per group."""
    b = eng.batch_size
    for g in mix["groups"]:
        eng.search(Q[:b], **g.get("query_params", {}))
    if mix["mode"] != "open":
        return None
    from repro.serve import AsyncEngine

    pump = config.get("pump", {})
    srv = AsyncEngine(eng, max_wait_ms=float(pump.get("max_wait_ms", 5.0)),
                      max_queue=int(pump.get("max_queue", 4096)))
    tickets = [srv.submit(Q[i], **g.get("query_params", {}))
               for i, g in enumerate(mix["groups"])]
    for t in tickets:
        t.result(timeout=300)
    return srv


def window(eng, srv, Q, mix, seconds, seed, trace):
    import numpy as np

    from chipbench import traffic

    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32,
                                 0x5EED])
    with traffic.span("cb.window", trace):
        if mix["mode"] == "closed":
            return traffic.closed_loop(eng, Q, mix, seconds, rng, trace)
        if mix["mode"] == "open":
            return traffic.open_loop(srv, Q, mix, seconds, rng, trace)
    raise ValueError(f"unknown traffic mode {mix['mode']!r}")


def use_cache(root: Path) -> None:
    """JAX's persistent compilation cache in ``<root>/.jax_cache``, through
    the program's own ``compile_cache``, never pruned: an evicted program
    (the generator's takes 15 s to compile for a v5e) would compile again
    in every run."""
    cache = root / ".jax_cache"
    cache.mkdir(exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(cache)
    from repro.core import compile_cache

    compile_cache.enable()
    import jax

    jax.config.update("jax_compilation_cache_max_size", -1)


def run(args, root: Path, accelerator) -> int:
    from chipbench import bench

    spec = bench.load_cell(root, args.workload)
    cell, config, mix = spec["cell"], spec["config"], spec["traffic"]
    if not (root / "src" / "repro").is_dir():
        print(f"chipbench: the system under test (src/repro) is not in "
              f"{root}", file=sys.stderr)
        return 2
    if str(root / "src") not in sys.path:
        sys.path.insert(0, str(root / "src"))
    use_cache(root)
    try:
        devices = accelerator(int(cell["chips"]))
    except NoAccelerator as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2

    import numpy as np

    import jax

    from chipbench import check, data, trace as tr

    dev = devices[0]
    counter = CompileCounter()
    t = time.perf_counter()
    X_dev, Q_dev = data.make(config["corpus"], args.seed)
    X = np.asarray(X_dev)
    Q = np.asarray(Q_dev)
    del X_dev, Q_dev
    corpus_s = time.perf_counter() - t
    t = time.perf_counter()
    eng = build_engine(config, mix, X)
    jax.block_until_ready(eng.state.arrays)
    build_s = time.perf_counter() - t
    t = time.perf_counter()
    srv = warm(eng, Q, mix, config)
    warm_s = time.perf_counter() - t
    batches0 = eng.stats["batches"]
    served0 = {n: srv.metrics.counter(n) for n in ("served", "batches")} \
        if srv is not None else {}
    setup_s = time.perf_counter() - T0
    pauses = GcPauses()
    counter.on = pauses.on = True
    pauses.t0 = time.perf_counter()
    summary = None
    if args.trace:
        out, summary = tr.capture(
            lambda: window(eng, srv, Q, mix, args.seconds, args.seed, True))
    else:
        out = window(eng, srv, Q, mix, args.seconds, args.seed, False)
    counter.on = pauses.on = False
    compiles = counter.count
    counter.close()
    pauses.close()
    stats = dev.memory_stats() or {}
    peak = int(stats.get("peak_bytes_in_use", 0))
    serve_counters = {}
    if srv is not None:
        srv.close()
        serve_counters = {n: srv.metrics.counter(n) - served0.get(n, 0)
                          for n in ("served", "batches", "padded",
                                    "timed_out", "rejected", "failed")}
    device_batches = eng.stats["batches"] - batches0
    batch_size = eng.batch_size
    del eng, srv
    gc.collect()

    t = time.perf_counter()
    groups = [check.group_key(g.get("query_params", {}))
              for g in mix["groups"]]
    answers = dict(out["answers"], groups=groups)
    X_ref = jax.device_put(X, dev)
    verdict = check.judge(X, X_ref, Q, answers, int(mix["k"]),
                          config["guarantees"], config["check"],
                          unanswered=out["failed"])
    del X_ref
    reference_s = time.perf_counter() - t

    log(cell=cell["name"], seed=args.seed, corpus_s=corpus_s,
        build_s=build_s, warm_s=warm_s, setup_s=setup_s,
        reference_s=reference_s, compiles_in_window=compiles,
        peak_bytes_in_use=peak, device_batches=device_batches,
        attempted=out["attempted"], failed=out["failed"],
        elapsed_s=out["elapsed_s"])
    full = [p for p in pauses.pauses if p[0] == 2]
    log(gc_collections=len(pauses.pauses),
        gc_s=sum(p[2] for p in pauses.pauses),
        gc_max_s=max((p[2] for p in pauses.pauses), default=0.0),
        gc_full_at_s_s=[[round(t, 3), round(d, 4)] for _, t, d in full][:12])
    if mix["mode"] == "closed":
        batch_ms = [1e3 * (e - s) for s, e in out["spans"]]
        p50 = bench.percentile(batch_ms, 50)
        slow = [[round(s, 3), round(ms, 1)]
                for (s, _), ms in zip(out["spans"], batch_ms) if ms > 2 * p50]
        log(batch_ms_p50=p50, batch_ms_p99=bench.percentile(batch_ms, 99),
            batch_ms_max=max(batch_ms), slow_batches=len(slow),
            slow_batches_at_s_ms=slow[:12])
    if mix["mode"] == "open":
        late = out["lateness_ms"]
        worst = int(np.argmax(late))
        log(generator_late_ms_p50=bench.percentile(late, 50),
            generator_late_ms_p99=bench.percentile(late, 99),
            generator_late_ms_max=float(late[worst]),
            generator_worst_due_s=float(out["due_s"][worst]),
            generator_late_over_50ms=int(np.sum(late > 50.0)),
            rejected=out["rejected"], serve_counters=serve_counters)

    record = {
        "cell": cell["name"], "config": config, "traffic": mix,
        "seed": args.seed, "seconds": args.seconds,
        "device_kind": dev.device_kind,
        "setup": {"corpus_s": corpus_s, "build_s": build_s,
                  "warm_s": warm_s, "setup_s": setup_s},
        "window": {k: v for k, v in out.items()
                   if k not in ("answers", "latency_ms", "lateness_ms")},
        "latency_ms": out.get("latency_ms"),
        "groups": [{"key": groups[g], "query_params": dict(
            config["index"].get("query", {}),
            **mix["groups"][g].get("query_params", {})),
            "answered": out["per_group"].get(g, 0)}
            for g in range(len(groups))],
        "device_batches": device_batches, "batch_size": batch_size,
        "serve_counters": serve_counters, "recall": verdict["recall"],
    }
    result = {"correct": verdict["correct"],
              "attempted": int(out["attempted"]),
              "failed": int(out["failed"])}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    if args.trace:
        red = tr.reduce(summary)
        metrics = {}
        for m in spec["per_layer"]:
            v = bench.reader(root, m["name"])(record, red)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        device.update(busy_s=red["busy_s"], window_s=red["window_s"])
        result.update(metrics=metrics, device=device, breakdown={
            "device_ops": red["device_ops"], "idle_gaps": red["idle_gaps"]})
    else:
        values = end_to_end(record, out, verdict)
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in spec["end_to_end"]}
        result.update(metrics=metrics, device=device)
    result["checks"] = {k: [_num(v), _num(lim)]
                        for k, (v, lim) in verdict["checks"].items()}
    for name, (v, lim) in verdict["checks"].items():
        rel = ">=" if name.startswith("recall.") else "<="
        print(f"check {name} {v!r} {rel} {lim!r}", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


def _num(v):
    return v if math.isfinite(v) else str(v)


def end_to_end(record: dict, out: dict, verdict: dict) -> dict:
    from chipbench import bench

    vals = {"setup_s": record["setup"]["setup_s"],
            "recall": verdict["recall"]}
    if record["traffic"]["mode"] == "closed":
        vals["qps"] = len(out["answers"]["qidx"]) / out["elapsed_s"]
    lat = out.get("latency_ms")
    if lat is not None and len(lat):
        vals["p50_ms"] = bench.percentile(lat, 50)
        vals["p99_ms"] = bench.percentile(lat, 99)
    return vals


def main(argv=None, root: Path = ROOT, accelerator=require_accelerator):
    return run(parse_args(argv), root, accelerator)


if __name__ == "__main__":
    sys.exit(main())
