#!/usr/bin/env python3
"""The control of a cell's check: the reference computed in bfloat16, the
precision below the float32 the program states, put in the program's place.

    python3 bench/control.py --workload <cell> --seeds 11 12 13

For each seed it generates the cell's lake and draws the requests a run of
that seed would send in ``run_seconds``, answers each with the reference in
bfloat16 and compares those answers with the float64 reference exactly as
a run's answers are compared (`bench/check.py`). Prints one JSON line per
seed: the compared numbers and whether the control came out correct (it
must not). Needs no accelerator: the program is not run.
"""
from __future__ import annotations

import argparse
import json
import sys
import types

import ml_dtypes
import numpy as np

import check
import corpus
import run

#: requests compared for a closed-loop cell, whose runs send as many as
#: the server answers
CLOSED_REQUESTS = 256


def control_numbers(cell: run.Cell, seed: int, seconds: float,
                    dtype=ml_dtypes.bfloat16) -> dict:
    tr = cell.traffic
    lake = corpus.generate(cell.config, int(tr["pool"]),
                           np.random.default_rng(seed))
    queries = lake.pool
    _, picks = run.draw_requests(tr, seed, seconds, len(queries))
    if tr["loop"] != "open":      # a closed loop's count is not drawn
        picks = picks[:CLOSED_REQUESTS]
    # `repro.engine.plans.Request`'s defaults, so the control runs without
    # the program
    req = types.SimpleNamespace(**{"alpha": 0.05, "min_sample": 3,
                                   **tr["request"]})
    ref = run.reference_answers(lake, queries, cell.config, req, picks)
    low = run.reference_answers(lake, queries, cell.config, req, picks,
                                dtype=dtype)
    numbers = check.compare(low, ref, tr["limits"], req.k)
    correct, _ = check.verdict(numbers, tr["limits"])
    return dict(seed=seed, requests=len(picks), correct=correct, **numbers)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    args = p.parse_args(argv)
    cell = run.load_cell(args.workload)
    seconds = json.loads((run.ROOT / "BENCHMARK.json").read_text())[
        "run_seconds"]
    for seed in args.seeds:
        print(json.dumps(control_numbers(cell, seed, seconds)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
