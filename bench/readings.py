#!/usr/bin/env python3
"""Readings that set the limits of the benchmark's `correct`: the program
and its controls, on many seeds in one process.

Each reading goes through the same window, sample and comparison as a run
of ``bench/run.py`` and prints one JSON line: the seed, the data seed,
``correct``, the checks and the end-to-end metrics. ``--as`` picks what
answers in the program's place:

- ``program``: the program as the configuration states it; with
  ``--data-seeds`` also on corpora and index builds other than the
  configuration's own.
- ``default_precision``: the program with JAX's default matmul precision,
  the path one precision below the configuration's float32: TPU matmuls
  take one bfloat16 pass, the kernel ranks rows by those products. It has
  to fail ``order_gap``.
- ``bf16_scan``: the plain reference computed in bfloat16, an exact scan
  with bfloat16 operands (float32 accumulation, `lax.top_k`) that returns
  those scores. It has to fail ``score_err``.

    python3 bench/readings.py --workload yahoo.b64 --as default_precision \\
        --seeds 11,12,13 --seconds 5

The index is built once per data seed and precision and shared by the
seeds. Needs the chip, as run.py does; the benchmark's own runs never run
it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from types import SimpleNamespace

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import run  # noqa: E402


def build_bf16(x: np.ndarray, cfg: dict) -> run.Program:
    """The bfloat16 exact scan over ``x``."""
    import jax
    import jax.numpy as jnp

    k = int(cfg["guarantee"]["k"])
    xd = jax.device_put(jnp.asarray(x, jnp.bfloat16))

    @jax.jit
    def topk(xs, q):
        s = jnp.dot(q.astype(jnp.bfloat16), xs.T,
                    preferred_element_type=jnp.float32)
        return jax.lax.top_k(s, k)

    def search(q):
        scores, ids = topk(xd, jnp.asarray(q))
        return SimpleNamespace(ids=np.asarray(ids, np.int64),
                               scores=np.asarray(scores, np.float32),
                               stats={"pages": 0})

    return run.Program(search=search, n_blocks=1, page_rows=x.shape[0])


def shared(build):
    """``build`` that keeps the last program for the same data seed and
    precision."""
    memo = {}

    def b(x, cfg):
        key = (cfg["data_seed"], cfg["matmul_precision"])
        if key not in memo:
            memo.clear()
            memo[key] = build(x, cfg)
        return memo[key]

    return b


BUILDS = {"program": run.build_program,
          "default_precision": run.build_program,
          "bf16_scan": build_bf16}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--as", dest="what", choices=sorted(BUILDS),
                   default="program")
    p.add_argument("--seeds", required=True,
                   help="comma-separated non-negative seeds")
    p.add_argument("--data-seeds", default=None,
                   help="comma-separated data seeds (default: the "
                   "configuration's own)")
    p.add_argument("--seconds", type=float, default=5.0)
    args = p.parse_args(argv)
    cell = run.load_cell(run.ROOT, args.workload)
    peaks = run._load_json(os.path.join(run.BENCH, "peaks.json"))
    run.enable_compile_cache(run.ROOT)
    try:
        device = run.chip(cell.chips, peaks)
    except run.Refused as e:
        run.log(f"refused: {e}")
        return 3
    if args.what == "default_precision":
        cell.config["matmul_precision"] = "default"
    data_seeds = ([cell.config["data_seed"]] if args.data_seeds is None
                  else [int(s) for s in args.data_seeds.split(",")])
    build = shared(BUILDS[args.what])
    for data_seed in data_seeds:
        cell.config["data_seed"] = data_seed
        for seed in (int(s) for s in args.seeds.split(",")):
            out = run.run_cell(cell, seed, args.seconds, False, dict(device),
                               peaks[device["kind"]], time.perf_counter(),
                               build=build)
            print(json.dumps({"as": args.what, "data_seed": data_seed,
                              "seed": seed, "correct": out["correct"],
                              "checks": out["checks"],
                              "metrics": out["metrics"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
