import os
if __name__ == "__main__":  # pragma: no cover - CLI entry only
    # The 512-host-device trick is only for the CLI's production-mesh
    # analysis; importers (the search benchmark pulls `kernel_cost`) must
    # NOT have their jax backend reconfigured as an import side effect.
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=512")
"""Roofline analysis (deliverable g).

XLA's cost_analysis counts a while-loop body once regardless of trip count,
so FLOPs/bytes/collective-bytes are measured on small UNROLLED variants
(scan_util.set_unroll) and extrapolated linearly in depth groups and
microbatches:

  all kinds : C(G1), C(G2); total(G) = C(G1) + (G-G1) (C(G2) - C(G1))
  (train variants run with microbatches=1: the total step work is
  microbatch-count independent — same tokens — modulo the optimizer,
  which is depth-extrapolated with everything else)

Depth group sizes: attn=1 layer, xlstm_7_1=8 layers, zamba2=shared_every
layers, encdec varies enc/dec separately. The sLSTM time recurrence cannot
be unrolled (seq_len steps); its FLOPs are added analytically
(`slstm_correction`). Terms use the v5e row of `PEAKS`: 197 TF/s bf16,
819 GB/s HBM, 50 GB/s/link ICI; collective wire-bytes = per-device result
bytes x ring factor (all-reduce 2x, others 1x).

  PYTHONPATH=src python -m repro.launch.roofline --all [--out results/roofline]
"""
import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402

from ..configs import ARCH_IDS, SHAPES, SHAPES_BY_NAME, get_config  # noqa: E402
from ..models import scan_util  # noqa: E402
from . import specs as specs_lib  # noqa: E402
from .mesh import make_production_mesh  # noqa: E402

# NOTE: `.dryrun` also mutates XLA_FLAGS at import; it is imported lazily
# inside `_cost` so `kernel_cost` importers keep their jax backend as-is.

# Per-chip peaks keyed by `jax.Device.device_kind` (source: Google Cloud
# documentation, "TPU v5e"). A device kind missing here is an error, never a
# default: a roofline share against another chip's peaks means nothing.
PEAKS = {
    "TPU v5 lite": {"flops": 197e12,      # bf16 FLOP/s
                    "hbm_bw": 819e9,      # HBM bytes/s
                    "hbm_bytes": 16e9,    # HBM capacity
                    "ici_bw": 50e9},      # bytes/s per ICI link
}
_V5E = PEAKS["TPU v5 lite"]   # the production-mesh cell analysis below
PEAK_FLOPS, HBM_BW, ICI_BW = _V5E["flops"], _V5E["hbm_bw"], _V5E["ici_bw"]
RING_FACTOR = {"all-reduce": 2.0, "all-gather": 1.0, "reduce-scatter": 1.0,
               "all-to-all": 1.0, "collective-permute": 1.0}


def kernel_cost(fn, *args):
    """Roofline terms of ONE jit-able callable on its example ``args``.

    Lowers + compiles ``fn`` (wrapping in `jax.jit` unless it already
    carries `.lower`) and reads XLA's cost_analysis — the same figures the
    cell-level analysis above uses, without the unroll/extrapolation
    machinery. Used by the search benchmark to report ACHIEVED bytes/flops
    next to the v5e roofline bound for the fused-verification graph.

    The figures are a compile-time STATIC UPPER BOUND, not a measurement:
    cost_analysis sums EVERY branch of a `lax.switch`/`lax.cond` (the fused
    drivers compile one branch per pow2 tile bucket, of which exactly one
    executes per round) and counts a while body once regardless of trip
    count. The returned record carries ``static_upper_bound: True`` so
    BENCH consumers do not read it as achieved traffic; for measured
    per-stage wall-clock against this bound use the offline cutout runner,
    `repro.tune.cutout.stage_records` (DESIGN.md §15).

    The peaks come from `PEAKS` by the default device's kind. On CPU the
    record carries no roofline terms (a CPU run has no device peak to be a
    share of); a TPU kind missing from `PEAKS` raises ``KeyError``.
    """
    jitted = fn if hasattr(fn, "lower") else jax.jit(fn)
    compiled = jitted.lower(*args).compile()
    cost = compiled.cost_analysis()
    flops = float(cost.get("flops", 0.0))
    nbytes = float(cost.get("bytes accessed", 0.0))
    dev = jax.devices()[0]
    out = {"flops": flops, "bytes": nbytes, "device_kind": dev.device_kind,
           "static_upper_bound": True}
    if dev.platform == "cpu":
        return out
    if dev.device_kind not in PEAKS:
        raise KeyError(f"no peak rates for device kind {dev.device_kind!r}; "
                       f"add it to roofline.PEAKS (known: {sorted(PEAKS)})")
    peak = PEAKS[dev.device_kind]
    t_comp = flops / peak["flops"]
    t_mem = nbytes / peak["hbm_bw"]
    out.update({"t_compute_s": t_comp, "t_memory_s": t_mem,
                "roofline_s": max(t_comp, t_mem),
                "bound": "compute" if t_comp >= t_mem else "memory"})
    return out


def _cost(cfg, shape, mesh, *, microbatches=None):
    """Compile one unrolled variant; return {flops, bytes, coll:{op:bytes}}."""
    fn, args, in_sh, out_sh = specs_lib.build_cell(
        cfg, shape, mesh, microbatch_override=microbatches)
    scan_util.set_unroll(True)
    try:
        lowered = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh).lower(*args)
    finally:
        scan_util.set_unroll(False)
    from .dryrun import parse_collectives
    compiled = lowered.compile()
    cost = compiled.cost_analysis()
    coll, _ = parse_collectives(compiled.as_text())
    return {"flops": float(cost.get("flops", 0.0)),
            "bytes": float(cost.get("bytes accessed", 0.0)),
            "coll": coll}


def _lin(c_lo, c_hi, steps_lo, steps_hi):
    """Per-extra-step delta of every cost field."""
    def d(a, b):
        return (b - a) / (steps_hi - steps_lo)
    coll = {k: d(c_lo["coll"].get(k, 0), c_hi["coll"].get(k, 0))
            for k in set(c_lo["coll"]) | set(c_hi["coll"])}
    return {"flops": d(c_lo["flops"], c_hi["flops"]),
            "bytes": d(c_lo["bytes"], c_hi["bytes"]), "coll": coll}


def _combine(base, body, n_extra):
    coll = {k: base["coll"].get(k, 0) + n_extra * body["coll"].get(k, 0)
            for k in set(base["coll"]) | set(body["coll"])}
    return {"flops": base["flops"] + n_extra * body["flops"],
            "bytes": base["bytes"] + n_extra * body["bytes"], "coll": coll}


def _group_info(cfg):
    """(group_layer_count, total_groups_float, variant_cfgs (G1, G2))."""
    if cfg.block_pattern == "xlstm_7_1":
        g = 8
        return g, cfg.n_layers / g, (dataclasses.replace(cfg, n_layers=8),
                                     dataclasses.replace(cfg, n_layers=16))
    if cfg.block_pattern == "zamba2":
        g = cfg.shared_attn_every
        return g, cfg.n_layers / g, (dataclasses.replace(cfg, n_layers=g),
                                     dataclasses.replace(cfg, n_layers=2 * g))
    if cfg.block_pattern == "encdec":
        return 1, None, None  # handled separately
    return 1, float(cfg.n_layers), (dataclasses.replace(cfg, n_layers=1),
                                    dataclasses.replace(cfg, n_layers=2))


def fused_memory_bytes(cfg, shape, mesh, microbatches):
    """Analytic per-chip HBM traffic assuming production kernel fusion.

    cost_analysis' "bytes accessed" sums operand/result bytes of every HLO
    op — in the unrolled jnp graph that counts flash-attention score tiles
    and gating intermediates that live in VMEM once the Pallas kernels
    (kernels/) fuse them. This model counts only the traffic that MUST hit
    HBM: parameters (per microbatch re-read), optimizer state, saved
    activations (remat=full saves layer inputs), logits, embeddings and KV
    caches. The HLO figure is reported alongside as an unfused upper bound.
    """
    import numpy as np
    from ..distributed import sharding as shard_lib
    chips = mesh.devices.size
    model_sz = shard_lib.axis_size(mesh, "model")
    dp = shard_lib.axis_size(mesh, shard_lib.dp_axes(mesh))
    n_params = cfg.param_count()
    p_loc = 2.0 * n_params / model_sz              # bf16 weights per chip
    d = cfg.d_model
    kh, dh = cfg.n_kv_heads, cfg.head_dim_
    v_loc = cfg.vocab_padded * 2.0 / model_sz      # bf16 logits row bytes/chip

    if shape.kind == "train":
        tokens_loc = shape.global_batch * shape.seq_len / dp
        mb_tokens = tokens_loc / microbatches
        act = 2.0 * mb_tokens * d                  # bf16 layer input
        n_layers = cfg.n_layers
        per_mb = (
            2.0 * p_loc                            # weights fwd + bwd-recompute
            + n_layers * act * 2                   # save + reload boundaries
            + n_layers * act * 8                   # fused layer io (qkv/mlp r/w)
            + mb_tokens * v_loc * 3                # logits write + CE read (f32)
        )
        opt = (4.0 * n_params / chips) * 6         # f32 g, mu, nu r/w (ZeRO)
        return microbatches * per_mb + opt + 2.0 * p_loc
    if shape.kind == "prefill":
        tokens_loc = shape.global_batch * shape.seq_len / dp
        cache = 2.0 * 2 * cfg.n_layers * tokens_loc * kh * dh / max(
            model_sz if kh % model_sz == 0 or dh % model_sz == 0 else 1, 1)
        return p_loc + tokens_loc * d * 2 * 10 + cache + tokens_loc / shape.seq_len * v_loc
    # decode: weights + full KV cache read per token + states
    b_loc = max(shape.global_batch / dp, 1)
    kv_len = min(shape.seq_len, cfg.window) if cfg.attn == "swa" else shape.seq_len
    n_kv_layers = {"attn": cfg.n_layers, "encdec": cfg.n_layers,
                   "zamba2": max(cfg.n_layers // cfg.shared_attn_every, 1),
                   "xlstm_7_1": 0}[cfg.block_pattern]
    kv_shard = model_sz if (kh % model_sz == 0 or dh % model_sz == 0) else (
        model_sz if shape.global_batch < dp else model_sz)
    cache = 2.0 * 2 * n_kv_layers * b_loc * kv_len * kh * dh / kv_shard
    state = 0.0
    if cfg.block_pattern == "zamba2":
        inner = cfg.ssm.expand * d
        state = 4.0 * 2 * cfg.n_layers * b_loc * inner * cfg.ssm.state_dim / cfg.ssm.head_dim / model_sz * cfg.ssm.head_dim
    if cfg.block_pattern == "xlstm_7_1":
        p = d // cfg.n_heads
        state = 4.0 * 2 * cfg.n_layers * b_loc * d * p / model_sz
    return p_loc + cache + state + b_loc * v_loc


def slstm_correction(cfg, shape):
    """Analytic FLOPs of the sLSTM time recurrence (not unrollable).

    Per step per layer: recurrent einsum 2*d*4p + ~24 elementwise ops on
    (h,p); times tokens processed."""
    if cfg.block_pattern != "xlstm_7_1":
        return 0.0
    d = cfg.d_model
    p = d // cfg.n_heads
    n_slstm = cfg.n_layers // 8
    per_tok = 2 * d * 4 * p + 24 * d
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 3.0 * per_tok * tokens * n_slstm  # fwd + bwd ~ 3x fwd
    if shape.kind == "prefill":
        return float(per_tok * shape.global_batch * shape.seq_len * n_slstm)
    return float(per_tok * shape.global_batch * n_slstm)


def _scale(total, factor):
    return {"flops": total["flops"] * factor, "bytes": total["bytes"] * factor,
            "coll": {k: v * factor for k, v in total["coll"].items()}}


def _measure_total(cfg, shape, mesh, mb1):
    """Depth-extrapolated costs for one (possibly seq-reduced) shape."""
    if cfg.block_pattern == "encdec":
        c11 = _cost(dataclasses.replace(cfg, enc_layers=1, n_layers=1), shape, mesh,
                    microbatches=mb1)
        c21 = _cost(dataclasses.replace(cfg, enc_layers=2, n_layers=1), shape, mesh,
                    microbatches=mb1)
        c12 = _cost(dataclasses.replace(cfg, enc_layers=1, n_layers=2), shape, mesh,
                    microbatches=mb1)
        enc_body, dec_body = _lin(c11, c21, 1, 2), _lin(c11, c12, 1, 2)
        return _combine(_combine(c11, enc_body, cfg.enc_layers - 1),
                        dec_body, cfg.n_layers - 1)
    g_layers, n_groups, (cfg1, cfg2) = _group_info(cfg)
    c1 = _cost(cfg1, shape, mesh, microbatches=mb1)
    c2 = _cost(cfg2, shape, mesh, microbatches=mb1)
    return _combine(c1, _lin(c1, c2, 1, 2), n_groups - 1)


def analyse_cell(arch_id, shape_name, mesh):
    cfg = get_config(arch_id)
    shape = SHAPES_BY_NAME[shape_name]
    ok, reason = specs_lib.cell_applicable(cfg, shape)
    if not ok:
        return {"arch": arch_id, "shape": shape_name, "status": reason}
    chips = mesh.devices.size

    mb1 = 1 if shape.kind == "train" else None
    long_seq = shape.kind in ("train", "prefill") and shape.seq_len > 2048
    if cfg.block_pattern == "xlstm_7_1" and long_seq:
        # sLSTM's time scan makes full-seq unrolled compiles infeasible;
        # every xLSTM term is linear in tokens -> measure short, scale.
        s1 = 512
        total = _measure_total(cfg, dataclasses.replace(shape, seq_len=s1),
                               mesh, mb1)
        total = _scale(total, shape.seq_len / s1)
    elif cfg.block_pattern == "zamba2" and long_seq:
        # mamba terms are linear in S, the shared attention quadratic:
        # two-point fit f(S) = a S + b S^2.
        s1, s2 = 1024, 2048
        f1 = _measure_total(cfg, dataclasses.replace(shape, seq_len=s1), mesh, mb1)
        f2 = _measure_total(cfg, dataclasses.replace(shape, seq_len=s2), mesh, mb1)

        def fit(v1, v2):
            b = (v2 / s2 - v1 / s1) / (s2 - s1)
            a = v1 / s1 - b * s1
            return max(a * shape.seq_len + b * shape.seq_len ** 2, 0.0)

        total = {"flops": fit(f1["flops"], f2["flops"]),
                 "bytes": fit(f1["bytes"], f2["bytes"]),
                 "coll": {k: fit(f1["coll"].get(k, 0), f2["coll"].get(k, 0))
                          for k in set(f1["coll"]) | set(f2["coll"])}}
    else:
        total = _measure_total(cfg, shape, mesh, mb1)

    total["flops"] += slstm_correction(cfg, shape) / chips

    # cost_analysis reports the PER-DEVICE (post-partition) program, so the
    # terms are per-chip quantities already (calibrated in EXPERIMENTS.md).
    mb = (specs_lib.choose_microbatches(cfg, shape, mesh)
          if shape.kind == "train" else 1)
    fused_bytes = fused_memory_bytes(cfg, shape, mesh, mb)
    t_comp = total["flops"] / PEAK_FLOPS
    t_mem_hlo = total["bytes"] / HBM_BW
    t_mem = fused_bytes / HBM_BW
    wire = sum(RING_FACTOR.get(op, 1.0) * b for op, b in total["coll"].items())
    t_coll = wire / ICI_BW  # per-device wire bytes over one link
    dominant = max(("compute", t_comp), ("memory", t_mem),
                   ("collective", t_coll), key=lambda kv: kv[1])[0]

    n_active = cfg.active_param_count()
    if shape.kind == "train":
        model_flops = 6.0 * n_active * shape.global_batch * shape.seq_len
    elif shape.kind == "prefill":
        model_flops = 2.0 * n_active * shape.global_batch * shape.seq_len
    else:
        model_flops = 2.0 * n_active * shape.global_batch
    bound = max(t_comp, t_mem, t_coll)
    return {
        "arch": arch_id, "shape": shape_name, "status": "ok",
        "kind": shape.kind, "chips": chips,
        "hlo_flops_per_chip": total["flops"], "hlo_bytes_per_chip": total["bytes"],
        "collective_bytes_per_chip": {k: round(v) for k, v in total["coll"].items()},
        "wire_bytes_per_chip": round(wire),
        "fused_bytes_per_chip": round(fused_bytes),
        "t_compute_s": t_comp, "t_memory_s": t_mem,
        "t_memory_hlo_upper_s": t_mem_hlo, "t_collective_s": t_coll,
        "dominant": dominant,
        "model_flops": model_flops,
        "useful_flops_frac": (model_flops / (total["flops"] * chips)
                              if total["flops"] else 0),
        "bound_mfu": (model_flops / (chips * PEAK_FLOPS)) / bound if bound else 0,
        "roofline_time_s": bound,
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="results/roofline")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    mesh = make_production_mesh(multi_pod=False)  # roofline is single-pod
    archs = ARCH_IDS if (args.all or not args.arch) else [args.arch]
    shapes = [s.name for s in SHAPES] if (args.all or not args.shape) else [args.shape]
    os.makedirs(args.out, exist_ok=True)
    for a in archs:
        for s in shapes:
            path = os.path.join(args.out, f"{a}__{s}.json")
            if os.path.exists(path) and not args.force:
                print(f"[skip] {a}/{s}")
                continue
            t0 = time.time()
            try:
                rec = analyse_cell(a, s, mesh)
            except Exception as e:
                rec = {"arch": a, "shape": s, "status": "error",
                       "error": f"{type(e).__name__}: {e}",
                       "traceback": traceback.format_exc()[-3000:]}
            with open(path, "w") as f:
                json.dump(rec, f, indent=1)
            if rec["status"] == "ok":
                print(f"[ok  ] {a}/{s} ({time.time()-t0:.0f}s) dom={rec['dominant']} "
                      f"t=({rec['t_compute_s']:.4f},{rec['t_memory_s']:.4f},"
                      f"{rec['t_collective_s']:.4f})s bound_mfu={rec['bound_mfu']:.3f}",
                      flush=True)
            else:
                print(f"[{rec['status'][:5]}] {a}/{s} {rec.get('error','')}", flush=True)


if __name__ == "__main__":
    main()
