#!/usr/bin/env python3
"""Find a cell's knee: the highest open-loop rate its server sustains.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds 10 \
        --rates 8 12 16 20

Sets the cell up once, as a run does, then offers its traffic at each rate
for ``--seconds`` and prints one JSON line per rate: p50 and p95 latency,
completed queries per second, how late the generator ran, and the share of
requests answered after the window closed (a backlog that grows). Used once
when a cell is defined; the cell's traffic file then fixes its rate.
"""
from __future__ import annotations

import argparse
import json
import sys

import numpy as np

import run


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, default=10.0)
    p.add_argument("--rates", type=float, nargs="+", required=True)
    args = p.parse_args(argv)
    cell = run.load_cell(args.workload)
    sys.path.insert(0, str(run.ROOT / "src"))
    from repro.launch.cache import place_compile_cache
    place_compile_cache()
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print("sweep: JAX finds no TPU", file=sys.stderr)
        return 1
    s = run.setup(cell, args.seed, dev)
    print(json.dumps(dict(ingest_s=s.ingest_s, warmup_s=s.warmup_s)),
          flush=True)
    for i, rate in enumerate(args.rates):
        rec = run.window(s, args.seed + i, args.seconds, False, rate=rate)
        e2e = run.end_to_end(rec)
        late = [r for r in rec["requests"]
                if r["done"] is None or r["done"] > rec["window_s"]]
        lag = [r["send"] - r["due"] for r in rec["requests"]]
        print(json.dumps(dict(
            rate=rate, attempted=len(rec["requests"]), p50_ms=e2e["p50_ms"],
            p95_ms=e2e["p95_ms"], qps=e2e["qps"],
            gen_lag_p95_ms=float(np.percentile(lag, 95)) * 1e3,
            sketch_build_ms=float(np.mean([r["sketch_s"] for r in
                                           rec["requests"]])) * 1e3,
            answered_late=len(late) / max(len(rec["requests"]), 1),
            coalesce=rec["scheduler"]["completed"]
            / max(rec["scheduler"]["batches"], 1),
            window_compiles=rec["compiles"]["cache_misses"]
            + rec["compiles"]["lowerings"])), flush=True)
    s.sched.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
