#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json`` at the checkout
root. It names a configuration, ``bench/configs/<config>.json`` (the corpus
and the guarantee it is answered under), and a traffic mix,
``bench/traffic/<traffic>.json`` (batch size, query stream, warm-up, check
sample). A per-layer metric is read by ``bench/metrics/<name>.py``. Nothing
here is specific to one cell.

One run: JAX's matmul precision is set to the configuration's; the corpus
is drawn from the configuration's ``data_seed`` and the query stream from
``--seed`` (`gen.py`); the index is built through ``repro.api.build`` with
the stated guarantee and options; the batch shape and every verify tile
the window can meet are warmed up; then a closed loop answers
back-to-back batches through ``Searcher.search`` for ``--seconds``
seconds, each batch ending with its answers on the host. After the window
the device's peak memory is read, the index is freed, and a sample of the
window's answers drawn from the seed is compared with the exact top-k of
`reference.py`. With ``--trace 1`` the window runs under the JAX profiler
and the per-layer metrics are read from the trace.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, ``breakdown`` (traced
runs) and ``checks``, the compared numbers with their limits. Those numbers
are also the last lines of standard error. Exit status 3, and no result
line, when JAX finds no TPU, fewer chips than the cell asks for, or a
device kind missing from ``bench/peaks.json``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()   # set-up is counted from here

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402
from typing import Callable, Optional  # noqa: E402

import numpy as np  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import gen  # noqa: E402
import reference  # noqa: E402
import trace_reduce  # noqa: E402

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# warm-up goes on past ``warmup_batches`` while a batch still compiles
WARMUP_MAX_FACTOR = 4


class Refused(Exception):
    """The machine cannot run the cell: no TPU, too few chips, or a device
    kind with no peaks. Exit status 3, no result line."""


@dataclass
class Cell:
    name: str
    config: dict
    traffic: dict
    chips: int
    end_to_end: list            # [{"name", "unit", ...}] this cell reports
    per_layer: list


@dataclass
class Program:
    """The system under test as the window drives it."""
    search: Callable            # (B, d) queries -> answer: .ids, .scores, .stats
    n_blocks: int
    page_rows: int
    # answers one warm-up batch in every other shape ``search`` can take
    warm: Callable = lambda queries: None


@dataclass
class Window:
    """What the measured window produced, and what the layer readers see."""
    batch: int
    seconds: float = 0.0
    latencies: list = field(default_factory=list)
    ids: list = field(default_factory=list)
    scores: list = field(default_factory=list)
    pages: int = 0
    compiles: int = 0

    @property
    def batches(self) -> int:
        return len(self.latencies)

    @property
    def queries(self) -> int:
        return self.batches * self.batch


@dataclass
class Run:
    """Everything a per-layer reader (``bench/metrics/<name>.py``) may read."""
    window: Window
    n_blocks: int
    page_rows: int
    d: int
    itemsize: int
    peak: dict
    build_s: float
    trace: Optional[trace_reduce.Trace] = None
    lo: float = 0.0              # traced window, on the trace's clock
    hi: float = 0.0


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(root: str, name: str) -> Cell:
    bench = _load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; cells: "
                         f"{', '.join(sorted(cells))}")
    w = cells[name]
    return Cell(
        name=name,
        config=_load_json(os.path.join(BENCH, "configs",
                                       w["config"] + ".json")),
        traffic=_load_json(os.path.join(BENCH, "traffic",
                                        w["traffic"] + ".json")),
        chips=int(w["chips"]),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)],
    )


def enable_compile_cache(root: str) -> str:
    """JAX's persistent compilation cache: ``$JAX_COMPILATION_CACHE_DIR``
    when set, else ``<checkout>/.jax_cache``, a fixed path (the path is part
    of the cache key)."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(root, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def chip(chips: int, peaks: dict) -> dict:
    """The device as JAX reports it; `Refused` unless it is a TPU with at
    least ``chips`` devices and a known kind."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise Refused(f"no TPU: JAX found platform {devs[0].platform!r}")
    if len(devs) < chips:
        raise Refused(f"the cell needs {chips} chips, JAX found {len(devs)}")
    kind = devs[0].device_kind
    if kind not in peaks:
        raise Refused(f"device kind {kind!r} is not in bench/peaks.json")
    return {"platform": devs[0].platform, "kind": kind, "count": len(devs)}


def metric_reader(name: str):
    path = os.path.join(BENCH, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class CompileCounter:
    """Counts JAX backend compilations (persistent-cache loads included)
    from its first use in the process."""
    n = 0
    _listening = False

    def __init__(self):
        import jax

        if not CompileCounter._listening:
            jax.monitoring.register_event_duration_secs_listener(self._on)
            CompileCounter._listening = True

    @staticmethod
    def _on(event: str, duration: float, **kwargs) -> None:
        if event == COMPILE_EVENT:
            CompileCounter.n += 1


def closed_loop(search, stream: np.ndarray, batch: int, seconds: float,
                compiles: CompileCounter, annotate=None) -> Window:
    """Back-to-back batches from ``stream`` until ``seconds`` have passed;
    every batch ends with its answers on the host."""
    win = Window(batch=batch)
    n_batches = len(stream) // batch
    c0 = compiles.n
    t0 = time.perf_counter()
    for i in range(n_batches):
        qb = stream[i * batch:(i + 1) * batch]
        t = time.perf_counter()
        if annotate is not None:
            with annotate("bench.batch"):
                res = search(qb)
        else:
            res = search(qb)
        done = time.perf_counter()
        win.latencies.append(done - t)
        win.ids.append(res.ids)
        win.scores.append(res.scores)
        win.pages += int(res.stats["pages"])
        if done - t0 >= seconds:
            win.seconds = done - t0
            win.compiles = compiles.n - c0
            return win
    raise RuntimeError(f"the query stream ran out after {n_batches} batches "
                       f"in {time.perf_counter() - t0:.3f} s: the traffic "
                       "file's stream_queries is too small for this window")


def end_to_end(name: str, setup_s: float, win: Window, recall: float,
               peak_bytes: int) -> float:
    if name == "setup_s":
        return setup_s
    if name == "qps":
        return win.queries / win.seconds
    if name == "p95_ms":
        return 1e3 * float(np.percentile(win.latencies, 95))
    if name == "recall_at_10":
        return recall
    if name == "peak_hbm_mb":
        return peak_bytes / 1e6
    raise KeyError(f"no end-to-end metric {name!r} in bench/run.py")


def tile_sizes(n_blocks: int) -> list:
    """Every verify tile a search round can take short of the whole index:
    the driver sizes a round's tile to the next power of two over the
    blocks its batch selected."""
    return [1 << j for j in range(n_blocks.bit_length())
            if (1 << j) < n_blocks]


def build_program(x: np.ndarray, cfg: dict) -> Program:
    """The system under test: ``repro.api.build`` over ``x`` under the
    configuration's guarantee, options and ``data_seed``, shipped to the
    device."""
    import jax

    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro import api

    searcher = api.build(x, backend="promips",
                         guarantee=api.GuaranteeConfig(**cfg["guarantee"]),
                         seed=cfg["data_seed"], **cfg["api_options"])
    jax.block_until_ready(searcher.pm.arrays)     # the index on the device
    meta = searcher.pm.meta

    def warm(queries):
        """Answers ``queries`` once with both rounds' tiles capped at each
        size of `tile_sizes`: a round whose selection is narrower than the
        warm-up batches' then finds its program compiled. The cap is the
        API's own ``RuntimeConfig.tile_cap``; the programs it compiles are
        the ones an uncapped round of that size runs."""
        for t in tile_sizes(meta.n_blocks):
            searcher.search(queries, runtime=dataclasses.replace(
                searcher.runtime, tile_cap=t))

    return Program(search=searcher.search, n_blocks=meta.n_blocks,
                   page_rows=meta.page_rows, warm=warm)


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             device: dict, peak: dict, t_start: float,
             build=build_program) -> dict:
    """Everything after the chip check. ``build(x, config)`` returns the
    `Program`; tests and the controls put another system in the program's
    place through it."""
    import jax

    cfg, traffic = cell.config, cell.traffic
    # the products' precision is part of the configuration: "highest" makes
    # TPU matmuls float32, the default runs them in bfloat16 passes
    jax.config.update("jax_default_matmul_precision", cfg["matmul_precision"])
    if (traffic["loop"], traffic["queries"]) != ("closed",
                                                 "in_subspace_users"):
        raise ValueError(f"traffic {traffic} is not one this harness drives")
    batch = int(traffic["batch"])
    compiles = CompileCounter()

    x, v, spec = gen.corpus(cfg)
    stream = gen.user_queries(v, spec, int(traffic["stream_queries"]), seed)
    warm = gen.user_queries(
        v, spec, WARMUP_MAX_FACTOR * int(traffic["warmup_batches"]) * batch,
        seed, stream=gen.WARMUP)
    log(f"{cell.name}: corpus {x.shape}, stream {stream.shape} "
        f"({time.perf_counter() - t_start:.3f} s)")

    t = time.perf_counter()
    program = build(x, cfg)
    build_s = time.perf_counter() - t
    log(f"build {build_s:.3f} s: {program.n_blocks} blocks of "
        f"{program.page_rows} rows")

    annotate = obs_trace = None
    if trace:
        annotate = jax.profiler.TraceAnnotation
        # the program's own spans, as unfenced host annotations
        from repro.obs import trace as obs_trace
        obs_trace.configure(enabled=True, fence=False, annotate=True)

    c0 = compiles.n
    program.warm(warm[:batch])
    n_warm = int(traffic["warmup_batches"])
    for i in range(WARMUP_MAX_FACTOR * n_warm):
        c = compiles.n
        program.search(warm[i * batch:(i + 1) * batch])
        if i + 1 >= n_warm and compiles.n == c:
            break
    log(f"warm-up {i + 1} batches and the tiles, {compiles.n - c0} "
        "compilations")

    log_dir = None
    if trace:
        log_dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(log_dir, profiler_options=opts)
    setup_s = time.perf_counter() - t_start
    if trace:
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
            win = closed_loop(program.search, stream, batch, seconds,
                              compiles, annotate)
        jax.profiler.stop_trace()
        obs_trace.configure(enabled=False, annotate=False)
    else:
        win = closed_loop(program.search, stream, batch, seconds, compiles)
    log(f"window {win.seconds:.3f} s: {win.batches} batches, "
        f"{win.compiles} compilations inside, batch median "
        f"{np.median(win.latencies):.6f} s, slowest "
        f"{max(win.latencies):.6f} s")

    peak_bytes = int(max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                         for d in jax.devices()))
    n_blocks, page_rows = program.n_blocks, program.page_rows
    del program                 # frees the index before the reference runs
    gc.collect()

    run = Run(window=win, n_blocks=n_blocks, page_rows=page_rows, d=x.shape[1],
              itemsize=x.dtype.itemsize, peak=peak, build_s=build_s)
    result = {}
    if trace:
        tr = trace_reduce.Trace.from_file(trace_reduce.find_xplane(log_dir))
        shutil.rmtree(log_dir)
        run.trace = tr
        run.lo, run.hi = tr.window()
        ops = tr.ops(run.lo, run.hi)
        busy = [trace_reduce.busy_ns(evs) for evs in ops.values()]
        device["busy_s"] = float(np.mean(busy)) / 1e9 if busy else 0.0
        device["window_s"] = (run.hi - run.lo) / 1e9
        result["breakdown"] = trace_reduce.breakdown(tr, run.lo, run.hi)

    ids = np.concatenate(win.ids)
    scores = np.concatenate(win.scores)
    n_check = min(int(traffic["check_sample"]), len(ids))
    pick = np.sort(gen.rng(seed, gen.SAMPLE).choice(len(ids), n_check,
                                                    replace=False))
    t = time.perf_counter()
    cmp = reference.compare(x, stream[pick], ids, scores, pick,
                            cfg["guarantee"])
    log(f"reference over {n_check} answers {time.perf_counter() - t:.3f} s")

    metrics = {}
    if trace:
        for m in cell.per_layer:
            value = metric_reader(m["name"])(run)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    else:
        for m in cell.end_to_end:
            metrics[m["name"]] = {
                "value": float(end_to_end(m["name"], setup_s, win,
                                          cmp["recall"], peak_bytes)),
                "unit": m["unit"]}
    device["memory_peak_bytes"] = peak_bytes
    checks = cmp["checks"]
    out = {"correct": all(c["ok"] for c in checks.values()),
           "attempted": win.queries, "failed": checks["invalid"]["value"],
           "metrics": metrics, "device": device}
    out.update(result)
    out["checks"] = {k: {"value": c["value"], "limit": c["limit"]}
                     for k, c in checks.items()}
    return out


def parse(argv):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    if args.seed < 0:
        p.error("--seed must be a non-negative integer")
    return args


def main(argv=None) -> int:
    args = parse(argv)
    cell = load_cell(ROOT, args.workload)
    peaks = _load_json(os.path.join(BENCH, "peaks.json"))
    enable_compile_cache(ROOT)
    try:
        device = chip(cell.chips, peaks)
    except Refused as e:
        log(f"refused: {e}")
        return 3
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace), device,
                   peaks[device["kind"]], T_START)
    for name, c in out["checks"].items():
        log(f"check {name}: {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
