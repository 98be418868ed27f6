# One function per paper table. Print ``name,us_per_call,derived`` CSV.
"""Benchmark harness: reproduces every evaluation axis of paper §VIII on
shape-matched synthetic proxies (see benchmarks/common.py for sizes).

  PYTHONPATH=src python -m benchmarks.run [--only fig5]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src")))
sys.path.insert(0, os.path.abspath(os.path.join(os.path.dirname(__file__), "..")))

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))

import functools  # noqa: E402
import subprocess  # noqa: E402
from datetime import datetime, timezone  # noqa: E402

from benchmarks import common  # noqa: E402
from benchmarks import paper_figures as F  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

_FORCE_HOST_DEVICES = "--xla_force_host_platform_device_count=8"


def _simulate_pod_off_tpu() -> None:
    """``--sharded`` without a TPU simulates a pod: re-exec this process
    with 8 forced host devices (XLA reads the flag once, when the backend
    starts). On a TPU the chips are the devices and nothing is forced."""
    import jax

    flags = os.environ.get("XLA_FLAGS", "")
    if jax.default_backend() == "tpu" or _FORCE_HOST_DEVICES in flags:
        return
    os.environ["XLA_FLAGS"] = f"{flags} {_FORCE_HOST_DEVICES}".strip()
    os.execv(sys.executable, [sys.executable] + sys.orig_argv[1:])

# Repo-root records the bench functions (re)write; every run APPENDS the
# fresh record to results/bench/history.jsonl with a timestamp, so the
# BENCH_*.json numbers gain a trajectory instead of being overwritten.
BENCH_FILES = ("BENCH_search.json", "BENCH_stream.json", "BENCH_api.json",
               "BENCH_sharded.json", "BENCH_obs.json", "BENCH_tune.json",
               "BENCH_robust.json", "BENCH_serve.json")


@functools.lru_cache(maxsize=1)
def _provenance() -> dict:
    """Code + toolchain identity stamped into every history record, so a
    number can always be traced back to the commit and jax build that
    produced it (computed once per process; 'unknown' outside a checkout)."""
    try:
        commit = subprocess.run(
            ["git", "rev-parse", "HEAD"], cwd=ROOT, capture_output=True,
            text=True, timeout=10, check=True).stdout.strip()
    except Exception:
        commit = "unknown"
    import jax
    return {"commit": commit, "jax_version": jax.__version__,
            "platform": jax.default_backend()}


def _append_history(out_dir: str, bench: str, rows, t_start: float) -> None:
    ts = datetime.now(timezone.utc).isoformat(timespec="seconds")
    entry = {"ts": ts, "bench": bench, **_provenance(),
             "rows": [{"name": n, "us_per_call": u, "derived": d}
                      for n, u, d in rows]}
    for fname in BENCH_FILES:
        path = os.path.join(ROOT, fname)
        if os.path.exists(path) and os.path.getmtime(path) >= t_start:
            with open(path) as f:
                entry.setdefault("records", {})[fname] = json.load(f)
    with open(os.path.join(out_dir, "history.jsonl"), "a") as f:
        f.write(json.dumps(entry) + "\n")

BENCHES = [
    ("fig4a_index_size", F.fig4a_index_size),
    ("fig4b_preprocessing_time", F.fig4b_preprocessing_time),
    ("fig5-9_ratio_recall_pages_time", F.fig5_6_overall_ratio_recall),
    ("fig10_impact_of_c", F.fig10_impact_of_c),
    ("fig11_impact_of_p", F.fig11_impact_of_p),
    ("table2_complexity_scaling", F.table2_complexity_scaling),
    ("ablation_beyond_paper", F.ablation_beyond_paper),
    ("search_runtime", F.bench_search_runtime),
    ("device_throughput", F.bench_device_throughput),
    ("stream_churn", lambda: F.bench_stream(quick=False)),
    ("api_registry", lambda: F.bench_api(quick=False)),
    ("sharded_fanout", lambda: F.bench_sharded(quick=False)),
    ("obs_breakdown", lambda: F.bench_obs(quick=False)),
    ("tune_autotuner", lambda: F.bench_tune(smoke=True)),
    ("robust_durability", lambda: F.bench_robust(quick=False)),
    ("serve_frontend", lambda: F.bench_serve(quick=False)),
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, help="substring filter")
    ap.add_argument("--out", default="results/bench")
    ap.add_argument("--quick", action="store_true",
                    help="fast smoke: host vs scan/batched/fused runtime "
                         "comparison plus the n=100k large-n point where "
                         "the fused path must beat the exact scan (writes "
                         "BENCH_search.json; ~30s)")
    ap.add_argument("--stream", action="store_true",
                    help="streaming-index smoke: insert throughput + search "
                         "latency vs delta fraction (writes BENCH_stream.json)")
    ap.add_argument("--api", action="store_true",
                    help="registry sweep: build time, on-disk index bytes, "
                         "us/query and recall vs exact for every registered "
                         "backend (writes BENCH_api.json)")
    ap.add_argument("--sharded", action="store_true",
                    help="sharded fan-out smoke: in-graph fused vs batched "
                         "verification inside shard_map at n=100k, us/query "
                         "and recall vs device count over 8 forced host "
                         "devices (writes BENCH_sharded.json)")
    ap.add_argument("--obs", action="store_true",
                    help="observability smoke: span-tracer overhead on/off "
                         "at smoke scale plus the per-phase latency "
                         "breakdown (frontend/prefilter/verify/merge) at "
                         "the large-n point, with a Chrome-trace export "
                         "(writes BENCH_obs.json)")
    ap.add_argument("--tune", action="store_true",
                    help="offline autotuner bench: coordinate-descent "
                         "tuning run on a temp cache, tuned-vs-hand-picked "
                         "interleaved ratio, parity + empty-cache-noop "
                         "audits (writes BENCH_tune.json)")
    ap.add_argument("--robust", action="store_true",
                    help="robustness smoke: WAL'd vs plain stream workload "
                         "overhead, crash-recovery wall time + replay "
                         "rows/s + bit-parity, and the serve degradation "
                         "ladder under open-loop overload with per-tier "
                         "p50/p99 + recall vs declared floors (writes "
                         "BENCH_robust.json)")
    ap.add_argument("--serve", action="store_true",
                    help="serve-frontend smoke: open-loop Zipfian ramp "
                         "through the degradation ladder (p50/p99 latency, "
                         "queue wait, qps, shed/expired fractions, cache "
                         "hit rate, tier occupancy), cache-on vs cache-off "
                         "throughput at saturation, cold-traffic cache "
                         "bit-parity and the inactive-slot page-accounting "
                         "check (writes BENCH_serve.json)")
    ap.add_argument("--smoke", action="store_true",
                    help="with --tune: smallest cutout + tightest budget "
                         "(the ci.sh tune tier)")
    args = ap.parse_args()
    if args.sharded:
        _simulate_pod_off_tpu()
    enable_compile_cache(ROOT)

    if args.quick:
        benches = [("search_runtime", lambda: F.bench_search_runtime(quick=True))]
    elif args.stream:
        benches = [("stream_churn", lambda: F.bench_stream(quick=True))]
    elif args.api:
        benches = [("api_registry", lambda: F.bench_api(quick=True))]
    elif args.sharded:
        benches = [("sharded_fanout", lambda: F.bench_sharded(quick=True))]
    elif args.obs:
        benches = [("obs_breakdown", lambda: F.bench_obs(quick=True))]
    elif args.tune:
        benches = [("tune_autotuner", lambda: F.bench_tune(smoke=args.smoke))]
    elif args.robust:
        benches = [("robust_durability", lambda: F.bench_robust(quick=True))]
    elif args.serve:
        benches = [("serve_frontend", lambda: F.bench_serve(quick=True))]
    else:
        benches = BENCHES
    os.makedirs(args.out, exist_ok=True)
    print("name,us_per_call,derived")
    for name, fn in benches:
        if args.only and args.only not in name:
            continue
        t0 = time.time()
        rows = fn()
        common.emit(rows)
        sys.stdout.flush()
        with open(os.path.join(args.out, f"{name}.json"), "w") as f:
            json.dump([{"name": n, "us_per_call": u, "derived": d}
                       for n, u, d in rows], f, indent=1)
        _append_history(args.out, name, rows, t0)
        print(f"# {name} done in {time.time()-t0:.1f}s", file=sys.stderr)


if __name__ == "__main__":
    main()
