"""Main experiment driver (the paper's ``run.py`` front-end).

Usage:
    PYTHONPATH=src python -m repro.core.runner \
        --dataset random-euclidean-10k --config src/repro/configs/ann_default.yaml \
        --count 10 --batch --out results/

Runs every expanded algorithm instance from the config against the dataset,
stores one result file per (instance, query-args) run, and prints the
frontier summary.
"""

from __future__ import annotations

import argparse
import sys
import time
from pathlib import Path
from typing import List, Optional, Sequence

from repro.core import compile_cache
from repro.core import config as config_mod
from repro.core import results as results_mod
from repro.core.experiment import (ExperimentSettings, check_can_isolate,
                                   held_backend, run_definition,
                                   run_in_child)
from repro.core.metrics import RunRecord
from repro.core.plotting import ascii_frontier
from repro.data.datasets import cache_path, get_dataset


DEFAULT_CONFIG = str(Path(__file__).resolve().parents[1]
                     / "configs" / "ann_default.yaml")


def _cache_dataset(name: str) -> None:
    get_dataset(name)          # builds and caches; returns nothing to pickle


def run_benchmark(
    dataset_name: str,
    config_source=None,
    *,
    count: int = 10,
    batch: bool = False,
    algorithms: Optional[Sequence[str]] = None,
    out_dir: Optional[str] = None,
    isolated: bool = False,
    timeout: Optional[float] = None,
    repetitions: int = 1,
    query_block: Optional[int] = None,
    verbose: bool = True,
) -> List[RunRecord]:
    if isolated:
        # the parent stays off the device: a child builds (and caches) the
        # dataset, whose ground truth runs on the device, and the parent
        # reads only the cached numpy arrays
        check_can_isolate(held_backend())
        if not cache_path(dataset_name).exists():
            run_in_child(_cache_dataset, (dataset_name,),
                         label=f"dataset {dataset_name}")
    dataset = get_dataset(dataset_name)
    definitions = config_mod.get_definitions(
        config_source or DEFAULT_CONFIG,
        point_type=dataset.point_type,
        metric=dataset.metric,
        dimension=dataset.dimension,
        count=count,
        algorithms=algorithms,
    )
    settings = ExperimentSettings(
        count=count, batch_mode=batch, isolated=isolated,
        timeout=timeout, repetitions=repetitions, query_block=query_block,
    )
    all_records: List[RunRecord] = []
    for definition in definitions:
        label = definition.instance_name
        t0 = time.perf_counter()
        try:
            # isolated children load the dataset by name themselves
            records = run_definition(
                definition, dataset_name if isolated else dataset, settings)
        except (TimeoutError, RuntimeError) as e:
            if verbose:
                print(f"  [FAIL] {label}: {e}", file=sys.stderr)
            continue
        if verbose:
            dt = time.perf_counter() - t0
            print(f"  [ok] {label}: {len(records)} runs in {dt:.1f}s")
        for record in records:
            if out_dir:
                results_mod.store(out_dir, record)
        all_records.extend(records)
    return all_records


def main(argv: Optional[Sequence[str]] = None) -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--dataset", required=True)
    p.add_argument("--config", default=DEFAULT_CONFIG)
    p.add_argument("--count", type=int, default=10)
    p.add_argument("--batch", action="store_true")
    p.add_argument("--algorithm", action="append", dest="algorithms")
    p.add_argument("--out", default="results")
    p.add_argument("--isolated", action="store_true")
    p.add_argument("--timeout", type=float, default=None)
    p.add_argument("--repetitions", type=int, default=1)
    p.add_argument("--query-block", type=int, default=None,
                   help="batch mode: stream queries in blocks of this size "
                        "(fixed memory for arbitrarily large query sets)")
    args = p.parse_args(argv)

    compile_cache.enable()
    records = run_benchmark(
        args.dataset, args.config, count=args.count, batch=args.batch,
        algorithms=args.algorithms, out_dir=args.out, isolated=args.isolated,
        timeout=args.timeout, repetitions=args.repetitions,
        query_block=args.query_block,
    )
    if records:
        print()
        print(ascii_frontier(records))


if __name__ == "__main__":
    main()
