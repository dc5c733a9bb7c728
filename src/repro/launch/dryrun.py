"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

``main`` forces 512 host devices through ``XLA_FLAGS`` before JAX first
initializes its backends (which fixes the device count); importing the
module changes no environment.

For every assigned architecture and its supported input shapes this driver:

  1. builds the step function (train / prefill / decode),
  2. builds ShapeDtypeStruct inputs + FSDP/TP/EP/SP NamedShardings,
  3. ``jax.jit(...).lower(...).compile()`` on the production mesh
     (16x16 single pod and 2x16x16 multi-pod),
  4. records ``memory_analysis()`` (fits-in-HBM proof),
     ``cost_analysis()`` (FLOPs / bytes) and the collective footprint
     parsed from the optimized HLO -> roofline terms (§Roofline).

Results append to a JSON report consumed by EXPERIMENTS.md.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun                 # all cells
  PYTHONPATH=src python -m repro.launch.dryrun --arch gemma3-4b \
      --shape train_4k --mesh single --report out.json
"""
from __future__ import annotations

import argparse
import json
import os
import time
import traceback
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs import ASSIGNED_ARCHS, get_config
from repro.distributed.sharding import (replicated, shard_batch, shard_cache,
                                        shard_params)
from repro.launch.mesh import describe, make_production_mesh
from repro.launch.roofline import (CHIPS_PER_POD, CollectiveStats, Roofline,
                                   model_flops, parse_collectives)
from repro.launch.shapes import (SHAPES, ShapeDef, batch_specs, cache_specs,
                                 supported_shapes)
from typing import Tuple
from repro.models import build_model
from repro.serve.steps import make_decode_step, make_prefill_step
from repro.train.optimizer import adamw
from repro.train.schedule import warmup_cosine
from repro.train.train_step import make_train_step

V5E_HBM = 16 * 1024 ** 3  # 16 GiB per chip


def _memory_analysis(compiled, chips: int = 1) -> Optional[Dict[str, float]]:
    """Per-device memory estimate.

    All sizes come from the SPMD-partitioned per-device executable
    (argument sizes match (params+opt)/chips).  ``temp_size`` on the CPU
    backend over-estimates a real TPU's footprint in two ways: buffers the
    TPU scheduler would reuse are counted live simultaneously, and
    involuntarily-replicated intermediates (visible as per-device flops
    above ideal) inflate it -- both are reported, and the fit check is
    evaluated against the *arguments + one microbatch activation* bound
    too (``fits_v5e_16gb_args``).
    """
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return None
    if ma is None:
        return None
    out = {}
    for k in ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "alias_size_in_bytes",
              "generated_code_size_in_bytes"):
        v = getattr(ma, k, None)
        if v is not None:
            out[k] = float(v)
    if out:
        live = (out.get("argument_size_in_bytes", 0)
                + out.get("output_size_in_bytes", 0)
                + out.get("temp_size_in_bytes", 0)
                - out.get("alias_size_in_bytes", 0))
        out["live_bytes_per_device"] = live
        out["fits_v5e_16gb"] = bool(live <= V5E_HBM)
        out["fits_v5e_16gb_args"] = bool(
            out.get("argument_size_in_bytes", 0)
            + out.get("output_size_in_bytes", 0)
            - out.get("alias_size_in_bytes", 0) <= V5E_HBM)
    return out


def _cost_analysis(compiled) -> Dict[str, float]:
    try:
        ca = compiled.cost_analysis()
    except Exception:
        return {}
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    return {k: float(v) for k, v in ca.items()
            if isinstance(v, (int, float))}


def moment_dtype_for(cfg) -> str:
    """Optimizer-state policy: int8 moments >=100B, bf16 >=10B, else fp32."""
    from repro.launch.roofline import active_params
    n = active_params(cfg)
    total = n  # dense ~= active; MoE far larger -> use analytic full count
    if cfg.moe:
        total = n + (cfg.moe.num_experts - cfg.moe.top_k) * 3 \
            * cfg.d_model * cfg.moe.d_expert * \
            sum(1 for s in (list(cfg.prefix) + list(cfg.unit) * cfg.n_units)
                if s.moe)
    if total > 100e9:
        return "int8"
    if total > 10e9:
        return "bfloat16"
    return "float32"


def build_cell(cfg, shape: ShapeDef, mesh, *, batch_override: int = None,
               train_opt_only: bool = False):
    """Returns (fn, args, in_shardings, donate) ready to lower."""
    model = build_model(cfg)
    params_sds = jax.eval_shape(lambda: model.init(0))
    b = batch_override or shape.batch
    import dataclasses as _dc
    shape = _dc.replace(shape, batch=b)

    if shape.kind == "train":
        opt = adamw(warmup_cosine(3e-4, 100, 10_000),
                    moment_dtype=moment_dtype_for(cfg))
        opt_sds = jax.eval_shape(opt.init, params_sds)
        if train_opt_only:
            # optimizer-update-only probe (separates update cost from loss)
            def fn(grads, state, params):
                return opt.update(grads, state, params)
            grads_sds = jax.tree.map(
                lambda p: jax.ShapeDtypeStruct(p.shape, jnp.float32),
                params_sds)
            in_sh = (shard_params(grads_sds, mesh),
                     shard_params(opt_sds, mesh),
                     shard_params(params_sds, mesh))
            return fn, (grads_sds, opt_sds, params_sds), in_sh, (1, 2)
        fn = make_train_step(model, opt, n_micro=cfg.train_microbatches,
                             accum_dtype=jnp.bfloat16
                             if cfg.param_dtype == "bfloat16"
                             else jnp.float32)
        batch = batch_specs(cfg, shape, with_labels=True)
        in_sh = (shard_params(params_sds, mesh),
                 shard_params(opt_sds, mesh),
                 shard_batch(batch, mesh, shape.batch))
        return fn, (params_sds, opt_sds, batch), in_sh, (0, 1)

    if shape.kind == "prefill":
        fn = make_prefill_step(model)
        batch = batch_specs(cfg, shape, with_labels=False)
        cache = cache_specs(model, cfg, shape)
        in_sh = (shard_params(params_sds, mesh),
                 shard_batch(batch, mesh, shape.batch),
                 shard_cache(cache, mesh, shape.batch))
        return fn, (params_sds, batch, cache), in_sh, (2,)

    # decode
    fn = make_decode_step(model)
    batch = batch_specs(cfg, shape, with_labels=False)
    cache = cache_specs(model, cfg, shape)
    in_sh = (shard_params(params_sds, mesh),
             shard_batch(batch, mesh, shape.batch),
             shard_cache(cache, mesh, shape.batch))
    return fn, (params_sds, batch["tokens"], cache), \
        (in_sh[0], in_sh[1]["tokens"], in_sh[2]), (2,)


def _compile_cell(cfg, shape, mesh, **kw):
    with jax.set_mesh(mesh):
        fn, args, in_sh, donate = build_cell(cfg, shape, mesh, **kw)
        jitted = jax.jit(fn, in_shardings=in_sh, donate_argnums=donate)
        return jitted.lower(*args).compile()


def _cell_costs(compiled, chips_per_pod) -> Dict[str, float]:
    cost = _cost_analysis(compiled)
    coll = parse_collectives(compiled.as_text(), chips_per_pod)
    return {"flops": cost.get("flops", 0.0),
            "bytes": cost.get("bytes accessed", 0.0),
            "ici": float(coll.ici_bytes), "dcn": float(coll.dcn_bytes),
            "coll_count": float(coll.count),
            "by_op": coll.by_op}


def _affine(c1: Dict, c2: Dict) -> Tuple[Dict, Dict]:
    """Per-unit slope and base from 1-unit / 2-unit probe costs."""
    keys = ("flops", "bytes", "ici", "dcn", "coll_count")
    slope = {k: max(c2[k] - c1[k], 0.0) for k in keys}
    base = {k: max(c1[k] - slope[k], 0.0) for k in keys}
    return base, slope


def probe_roofline(cfg, shape: ShapeDef, mesh, chips_per_pod) -> Dict:
    """Reconstruct true per-step costs from unrolled 1/2-unit probes.

    XLA's cost_analysis counts while-loop bodies once, so the scanned
    production executable under-reports loop costs.  Probes with unrolled
    units (full layer dims!) give exact per-unit costs; full-model cost is
    affine: base + n_units * unit.  Train cells additionally separate the
    optimizer update (probed standalone) and scale the loss part by the
    microbatch count.
    """
    p1, p2 = cfg.probe(1), cfg.probe(2)
    micro_b = (shape.batch // cfg.train_microbatches
               if shape.kind == "train" else None)
    c1 = _cell_costs(_compile_cell(p1, shape, mesh, batch_override=micro_b),
                     chips_per_pod)
    c2 = _cell_costs(_compile_cell(p2, shape, mesh, batch_override=micro_b),
                     chips_per_pod)
    base, slope = _affine(c1, c2)
    n = cfg.n_units
    keys = ("flops", "bytes", "ici", "dcn", "coll_count")
    if shape.kind != "train":
        return {k: base[k] + n * slope[k] for k in keys}
    o1 = _cell_costs(_compile_cell(p1, shape, mesh, batch_override=micro_b,
                                   train_opt_only=True), chips_per_pod)
    o2 = _cell_costs(_compile_cell(p2, shape, mesh, batch_override=micro_b,
                                   train_opt_only=True), chips_per_pod)
    obase, oslope = _affine(o1, o2)
    out = {}
    for k in keys:
        opt_full = obase[k] + n * oslope[k]
        loss_full = max(base[k] + n * slope[k] - opt_full, 0.0)
        out[k] = cfg.train_microbatches * loss_full + opt_full
    return out


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             mesh_factory=make_production_mesh,
             with_probes: bool = True) -> Dict:
    mesh = mesh_factory(multi_pod=multi_pod)
    chips = int(len(mesh.devices.reshape(-1)))
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    chips_per_pod = CHIPS_PER_POD if multi_pod else chips + 1

    # 1) production compile (scanned): proves lowering + memory fit
    t0 = time.time()
    compiled = _compile_cell(cfg, shape, mesh)
    elapsed = time.time() - t0
    memory = _memory_analysis(compiled, chips)
    raw = _cell_costs(compiled, chips_per_pod)

    # 2) cost probes (unrolled): true roofline terms
    costs = probe_roofline(cfg, shape, mesh, chips_per_pod) \
        if with_probes else raw

    coll = CollectiveStats(ici_bytes=int(costs["ici"]),
                           dcn_bytes=int(costs["dcn"]),
                           by_op=raw["by_op"], count=int(costs["coll_count"]))
    rf = Roofline(
        arch=arch, shape=shape_name,
        mesh=("2x16x16" if multi_pod else "16x16")
        if mesh_factory is make_production_mesh else describe(mesh),
        chips=chips,
        # calibration (EXPERIMENTS.md §Methodology): cost_analysis is
        # computed on the SPMD-partitioned per-device module (verified:
        # unsharded compile of the same probe reports ~chips x more);
        # involuntary replication therefore shows up as per-device flops
        # above ideal -- exactly what the perf loop drives down.
        flops_per_device=costs["flops"],
        bytes_per_device=costs["bytes"],
        coll=coll,
        model_flops=model_flops(cfg, shape.kind, shape.batch, shape.seq),
        per_device_memory=memory)
    row = rf.row()
    row.update({"status": "ok", "compile_s": elapsed,
                "coll_by_op": raw["by_op"],
                "raw_scanned_flops_per_dev": raw["flops"],
                "probes": bool(with_probes)})
    return row


def main(argv=None) -> None:
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    if "--xla_force_host_platform_device_count" not in os.environ.get(
            "XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=512").strip()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, help="single arch id")
    ap.add_argument("--shape", default=None, help="single shape id")
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--report", default="dryrun_report.json")
    args = ap.parse_args(argv)

    archs = [args.arch] if args.arch else list(ASSIGNED_ARCHS)
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    rows = []
    if os.path.exists(args.report):
        with open(args.report) as f:
            rows = json.load(f)
    done = {(r["arch"], r["shape"], r["mesh"]) for r in rows
            if r.get("status") == "ok"}

    for arch in archs:
        cfg = get_config(arch)
        shapes = ([args.shape] if args.shape
                  else supported_shapes(cfg))
        for shape_name in shapes:
            for multi in meshes:
                mesh_id = "2x16x16" if multi else "16x16"
                if (arch, shape_name, mesh_id) in done:
                    print(f"[skip] {arch} {shape_name} {mesh_id} (cached)")
                    continue
                tag = f"{arch} | {shape_name} | {mesh_id}"
                print(f"[lower+compile] {tag} ...", flush=True)
                try:
                    # roofline probes on the single-pod mesh only (the
                    # multi-pod pass proves the 'pod' axis shards)
                    row = run_cell(arch, shape_name, multi,
                                   with_probes=not multi)
                    print(f"  ok in {row['compile_s']:.1f}s  "
                          f"bottleneck={row['bottleneck']}  "
                          f"t=(c {row['t_compute_s']:.3e}, "
                          f"m {row['t_memory_s']:.3e}, "
                          f"x {row['t_collective_s']:.3e})s  "
                          f"useful={row['useful_flops_ratio']:.2f}",
                          flush=True)
                except Exception as e:  # a failure here is a system bug
                    row = {"arch": arch, "shape": shape_name,
                           "mesh": mesh_id, "status": "FAIL",
                           "error": f"{type(e).__name__}: {e}",
                           "traceback": traceback.format_exc()[-2000:]}
                    print(f"  FAIL: {row['error']}", flush=True)
                rows = [r for r in rows
                        if (r["arch"], r["shape"], r["mesh"])
                        != (arch, shape_name, mesh_id)]
                rows.append(row)
                with open(args.report, "w") as f:
                    json.dump(rows, f, indent=1, default=str)

    ok = sum(1 for r in rows if r.get("status") == "ok")
    fail = sum(1 for r in rows if r.get("status") != "ok")
    print(f"\n== dry-run complete: {ok} ok, {fail} failed -> {args.report}")
    if fail:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
