#!/usr/bin/env python3
"""Readings that the limits of ``check.py`` are set from, on the chip.

    python chipbench/readings.py --workload <cell> --seeds 1,2,3 \\
        [--seconds 3] [--control-only]

For each seed, in one process: the cell's corpus, index and warm-up as a
run makes them, a short window of the cell's own traffic, and the
comparison of its answers (the program's reading); then the control, the
reference computed as float32 at ``high`` precision (three bf16 passes),
answering the same queries in the program's place, judged the same way
(the control's reading).  One JSON line per seed and side.  The
benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--seconds", type=float, default=3.0)
    args = p.parse_args(argv)
    from chipbench import run

    run.use_cache(ROOT)
    import numpy as np

    from chipbench import bench, check, data, reference

    spec = bench.load_cell(ROOT, args.workload)
    config, mix = spec["config"], spec["traffic"]
    run.require_accelerator(int(spec["cell"]["chips"]))
    groups = [check.group_key(g.get("query_params", {}))
              for g in mix["groups"]]
    k = int(mix["k"])
    for seed in [int(s) for s in args.seeds.split(",")]:
        X_dev, Q_dev = data.make(config["corpus"], seed)
        X, Q = np.asarray(X_dev), np.asarray(Q_dev)
        eng = run.build_engine(config, mix, X)
        srv = run.warm(eng, Q, mix, config)
        out = run.window(eng, srv, Q, mix, args.seconds, seed, False)
        if srv is not None:
            srv.close()
        del eng, srv
        answers = dict(out["answers"], groups=groups)
        t = time.perf_counter()
        prog = check.judge(X, X_dev, Q, answers, k, config["guarantees"],
                           config["check"], unanswered=out["failed"])
        ref_s = time.perf_counter() - t
        print(json.dumps({"cell": args.workload, "seed": seed,
                          "side": "program", "answers": len(answers["qidx"]),
                          "reference_s": ref_s, **prog}), flush=True)
        d, i = reference.control(X_dev, Q, answers["qidx"], k)
        ctrl = check.judge(X, X_dev, Q, dict(answers, ids=i, dists=d), k,
                           config["guarantees"], config["check"],
                           unanswered=0)
        print(json.dumps({"cell": args.workload, "seed": seed,
                          "side": "control", **ctrl}), flush=True)
        del X_dev, Q_dev
    return 0


if __name__ == "__main__":
    sys.exit(main())
