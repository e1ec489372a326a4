"""The experiment loop (paper §3.4, Figure 2).

Two phases per algorithm instance:

  1. *preprocessing phase*: ``fit(X)`` is timed -> build_time; the index
     size is measured afterwards.
  2. *query phase*: for each expanded ``query-args`` group, the instance is
     reconfigured via ``set_query_arguments`` and the full query set is run
     (single-query mode: one timed call per query; batch mode §3.5: one
     timed ``batch_query`` for the whole set, results materialised off the
     clock via ``get_batch_results``).

Isolation: the paper runs every instance in its own Docker container.  Here
each instance can run in a spawned subprocess (``isolated=True``) — same
crash/timeout containment and clean teardown semantics, no Docker dependency
(the paper's "local mode").  Memory use of the index is measured as the
RSS delta around fit() in that subprocess, alongside the structural
``index_size()``.  An accelerator belongs to one process at a time, so the
parent of an isolated run must not hold one: given a dataset *name*, the
child loads the dataset itself, and a parent that already holds a non-CPU
backend is refused (:func:`check_can_isolate`).
"""

from __future__ import annotations

import dataclasses
import multiprocessing as mp
import time
import traceback
from typing import Any, Callable, Dict, List, Optional, Sequence, Union

import numpy as np

from repro.core.config import Definition, instantiate
from repro.core.metrics import RunRecord
from repro.data.datasets import Dataset, get_dataset


@dataclasses.dataclass
class ExperimentSettings:
    count: int = 10                   # k
    batch_mode: bool = False
    repetitions: int = 1              # best-of-n for the query phase
    timeout: Optional[float] = None   # seconds for build+queries, isolated only
    isolated: bool = False            # subprocess isolation (Docker analogue)
    recompute_distances: bool = True
    # batch mode only: stream the query set through the algorithm in blocks
    # of this many queries, so arbitrarily large query sets run in fixed
    # memory (results are materialised off the clock after each block).
    query_block: Optional[int] = None
    # batch mode only: when every varying query-args position is a
    # traced-capable knob, run the WHOLE expanded query-args grid through
    # one vmapped search_sweep device call instead of the per-group loop
    # (per-group total_time is then the uniform share of the fused call).
    grid_sweep: bool = True


def _rss_kb() -> float:
    try:
        with open("/proc/self/status") as fh:
            for line in fh:
                if line.startswith("VmRSS"):
                    return float(line.split()[1])
    except OSError:
        pass
    return float("nan")


def run_definition(
    definition: Definition,
    dataset: Union[Dataset, str],
    settings: ExperimentSettings,
) -> List[RunRecord]:
    """Run one algorithm instance through the full experiment loop.

    ``dataset`` may be a registered dataset name: the process that runs
    the instance (the child, when isolated) then loads it itself.
    """
    if settings.isolated:
        return _run_isolated(definition, dataset, settings)
    if isinstance(dataset, str):
        dataset = get_dataset(dataset)
    return _run_local(definition, dataset, settings)


def _run_local(definition, dataset, settings) -> List[RunRecord]:
    algo = instantiate(definition)
    try:
        return _experiment_loop(algo, definition, dataset, settings)
    finally:
        algo.done()


def _experiment_loop(algo, definition, dataset, settings) -> List[RunRecord]:
    X, Q = dataset.train, dataset.test
    k = settings.count

    rss_before = _rss_kb()
    t0 = time.perf_counter()
    algo.fit(X)
    build_time = time.perf_counter() - t0
    rss_after = _rss_kb()

    index_size_kb = algo.index_size()
    records: List[RunRecord] = []

    qgroups: Sequence[tuple] = definition.query_argument_groups or ((),)
    if (settings.grid_sweep and settings.batch_mode and len(qgroups) > 1
            and not settings.query_block
            and hasattr(algo, "plan_query_sweep")):
        # Grid fast path: every varying query-args position is a traced
        # knob, so the whole expanded grid is ONE vmapped device call
        # (search_sweep_points) instead of a per-group query phase.
        plan = algo.plan_query_sweep(qgroups)
        if plan is not None:
            return _grid_query_phase(
                algo, definition, dataset, settings, qgroups, plan,
                build_time, index_size_kb, rss_after - rss_before)
    if len(qgroups) > 1 and hasattr(algo, "prepare_query_sweep"):
        # Traced-knob sweep (paper §2.2's per-query-args reconfiguration,
        # minus the recompilation): pin each sweepable knob's static cap to
        # the max across groups so ONE jit trace serves every group below.
        algo.prepare_query_sweep(qgroups)
    for qargs in qgroups:
        if qargs:
            algo.set_query_arguments(*qargs)
        best: Optional[Dict[str, Any]] = None
        for _ in range(max(1, settings.repetitions)):
            res = _query_phase(algo, Q, k, settings.batch_mode,
                               settings.query_block)
            if best is None or res["total_time"] < best["total_time"]:
                best = res
        assert best is not None
        neighbors = _pad_neighbors(best["results"], k)
        distances = _distances_for(dataset, neighbors) \
            if settings.recompute_distances else np.full(neighbors.shape, np.nan,
                                                         np.float32)
        attrs = dict(algo.get_additional())
        attrs["rss_delta_kb"] = rss_after - rss_before
        records.append(
            RunRecord(
                algorithm=definition.algorithm,
                instance_name=algo.name or definition.instance_name,
                query_arguments=tuple(qargs),
                dataset=dataset.name,
                count=k,
                batch_mode=settings.batch_mode,
                neighbors=neighbors,
                distances=distances,
                gt_neighbors=dataset.neighbors[:, :max(k, 1)],
                gt_distances=dataset.distances[:, :max(k, 1)],
                query_times=best["query_times"],
                total_time=best["total_time"],
                build_time=build_time,
                index_size_kb=index_size_kb,
                attrs=attrs,
            )
        )
    return records


def _grid_query_phase(algo, definition, dataset, settings, qgroups, plan,
                      build_time, index_size_kb, rss_delta) -> List[RunRecord]:
    """Batch-mode query phase for a whole query-args grid at once.

    One timed ``run_query_sweep`` device call answers every group (results
    are materialised off the clock, paper §3.5); each group still emits its
    own :class:`RunRecord`, with ``total_time`` the uniform share of the
    fused call — inside the vmapped trace every combination runs at the
    cap-sized window, so equal attribution is the honest split.
    """
    Q = dataset.test
    k = settings.count
    points, fixed = plan
    best: Optional[tuple] = None
    for _ in range(max(1, settings.repetitions)):
        t0 = time.perf_counter()
        dists, ids = algo.run_query_sweep(Q, k, points, fixed)
        dt = time.perf_counter() - t0
        if best is None or dt < best[0]:
            best = (dt, ids)
    assert best is not None
    total_time, ids = best
    ids = np.asarray(ids)                       # off the clock
    per_group = total_time / len(qgroups)
    records: List[RunRecord] = []
    for g, qargs in enumerate(qgroups):
        neighbors = _pad_neighbors(ids[g], k)
        distances = _distances_for(dataset, neighbors) \
            if settings.recompute_distances else np.full(neighbors.shape,
                                                         np.nan, np.float32)
        attrs = dict(algo.get_additional())
        # the per-algo dist_comps counters accumulate in query/batch_query,
        # which the fused sweep bypasses — a literal 0 would win every
        # distcomps frontier, so report "not measured" (NaN) instead
        attrs.pop("dist_comps", None)
        attrs["rss_delta_kb"] = rss_delta
        attrs["grid_sweep"] = True
        records.append(
            RunRecord(
                algorithm=definition.algorithm,
                instance_name=algo.name or definition.instance_name,
                query_arguments=tuple(qargs),
                dataset=dataset.name,
                count=k,
                batch_mode=True,
                neighbors=neighbors,
                distances=distances,
                gt_neighbors=dataset.neighbors[:, :max(k, 1)],
                gt_distances=dataset.distances[:, :max(k, 1)],
                query_times=np.empty(0, np.float64),
                total_time=per_group,
                build_time=build_time,
                index_size_kb=index_size_kb,
                attrs=attrs,
            )
        )
    return records


def _query_phase(algo, Q: np.ndarray, k: int, batch: bool,
                 query_block: Optional[int] = None) -> Dict[str, Any]:
    if batch:
        if query_block and 0 < query_block < len(Q):
            # query-streaming mode: fixed-memory blocks; the clock runs only
            # during each block's batch_query (materialisation stays off the
            # clock, per paper §3.5).
            total = 0.0
            chunks = []
            for s in range(0, len(Q), query_block):
                t0 = time.perf_counter()
                algo.batch_query(Q[s:s + query_block], k)
                total += time.perf_counter() - t0
                chunks.append(np.asarray(algo.get_batch_results()))
            return {"results": np.concatenate(chunks, axis=0),
                    "total_time": total,
                    "query_times": np.empty(0, np.float64)}
        t0 = time.perf_counter()
        algo.batch_query(Q, k)
        total = time.perf_counter() - t0
        # Materialisation happens OFF the clock (paper §3.5: opaque result +
        # additional call "will stop the clock").
        results = algo.get_batch_results()
        return {"results": results, "total_time": total,
                "query_times": np.empty(0, np.float64)}
    times = np.empty(len(Q), np.float64)
    results = []
    t0 = time.perf_counter()
    for i, q in enumerate(Q):
        s = time.perf_counter()
        results.append(np.asarray(algo.query(q, k)))
        times[i] = time.perf_counter() - s
    total = time.perf_counter() - t0
    return {"results": results, "total_time": total, "query_times": times}


def _pad_neighbors(results: Any, k: int) -> np.ndarray:
    """Normalise per-query results to an [nq, k] int64 array, -1 padded."""
    if isinstance(results, np.ndarray) and results.ndim == 2:
        out = results.astype(np.int64)
        if out.shape[1] >= k:
            return out[:, :k]
        pad = np.full((out.shape[0], k - out.shape[1]), -1, np.int64)
        return np.concatenate([out, pad], axis=1)
    rows = []
    for r in results:
        r = np.asarray(r, np.int64).ravel()[:k]
        if r.size < k:
            r = np.concatenate([r, np.full(k - r.size, -1, np.int64)])
        rows.append(r)
    return np.stack(rows) if rows else np.empty((0, k), np.int64)


def _distances_for(dataset: Dataset, neighbors: np.ndarray) -> np.ndarray:
    """Framework-side re-computation of result distances (paper §3.6)."""
    from repro.ann import distances as D

    return D.pairwise_rows(dataset.test, dataset.train, neighbors,
                           dataset.metric)


# --------------------------------------------------------------------------
# subprocess isolation (the Docker-container analogue)
# --------------------------------------------------------------------------

def held_backend() -> Optional[str]:
    """Platform of the JAX backend this process has initialized, or None
    if it has initialized none (importing jax initializes nothing)."""
    from jax._src import xla_bridge

    if not xla_bridge.backends_are_initialized():
        return None
    import jax

    return jax.default_backend()


def check_can_isolate(backend: Optional[str]) -> None:
    """Refuse isolation from a parent that holds an accelerator.

    An isolated child needs the device, and a device belongs to the one
    process that initialized its backend: a child spawned by such a parent
    fails or hangs waiting for it.  ``backend`` is :func:`held_backend`'s
    answer; a parent on the CPU backend (or on none) may isolate.
    """
    if backend is not None and backend != "cpu":
        raise RuntimeError(
            f"isolated runs need the parent process to stay off the "
            f"accelerator, but this process already holds the {backend!r} "
            f"backend, so a child process cannot get the device; run "
            f"without isolation, or start the isolated run from a process "
            f"that has not used JAX (pass the dataset by name)")


def _child(conn, target, args, cache_on):
    try:
        if cache_on:
            from repro.core import compile_cache

            compile_cache.enable()
        conn.send(("ok", target(*args)))
    except Exception:
        conn.send(("error", traceback.format_exc()))
    finally:
        conn.close()


def run_in_child(target: Callable, args: tuple, *, label: str,
                 timeout: Optional[float] = None):
    """``target(*args)`` in a spawned child process; returns its result.

    Raises ``RuntimeError`` naming ``label`` if the child raised or died
    without reporting, and ``TimeoutError`` (after terminating it) if it
    ran past ``timeout`` seconds.
    """
    import jax

    check_can_isolate(held_backend())
    # the child compiles into the parent's persistent cache, if it has one
    cache_on = bool(jax.config.jax_compilation_cache_dir)
    # spawn, not fork: jax's internal threads deadlock forked children
    ctx = mp.get_context("spawn")
    parent, child = ctx.Pipe()
    proc = ctx.Process(target=_child, args=(child, target, args, cache_on))
    proc.start()
    child.close()
    if parent.poll(timeout):
        # poll() also returns True when the pipe hits EOF — a child killed
        # mid-run (OOM, SIGKILL, hard crash in a C extension) closes the
        # pipe without sending anything, and recv() then raises EOFError.
        try:
            status, payload = parent.recv()
        except EOFError:
            proc.join()
            raise RuntimeError(
                f"isolated run of {label} died before reporting a result "
                f"(exit code {proc.exitcode}; OOM kill or crash in native "
                f"code?)") from None
        proc.join()
        if status == "error":
            raise RuntimeError(f"isolated run of {label} failed:\n{payload}")
        return payload
    # Timeout exceeded: terminate the container-equivalent (paper §3.4:
    # "perform a blocking, timed wait on the container, and will terminate
    # it if the user-configurable timeout is exceeded").
    proc.terminate()
    proc.join()
    raise TimeoutError(f"{label} exceeded timeout of {timeout}s")


def _run_isolated(definition, dataset, settings) -> List[RunRecord]:
    local = dataclasses.replace(settings, isolated=False)
    return run_in_child(run_definition, (definition, dataset, local),
                        label=definition.instance_name,
                        timeout=settings.timeout)
