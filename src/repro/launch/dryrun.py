import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture × input shape)
on the production meshes and extract the roofline terms.

The two lines above MUST stay first: jax locks the device count on first
init, and the dry-run (and only the dry-run) needs 512 placeholder host
devices so ``jax.make_mesh`` can build the 16×16 and 2×16×16 meshes.

Per cell this:
  1. builds abstract inputs (ShapeDtypeStruct — no allocation),
  2. ``jax.jit(step, in_shardings=…).lower(...).compile()`` under the mesh,
  3. prints ``compiled.memory_analysis()`` (fits-HBM proof) and
     ``cost_analysis()`` (FLOPs/bytes for §Roofline),
  4. scans the post-SPMD HLO for collective bytes,
  5. emits the roofline report + the TALP analytical device metrics
     (the paper's Device PE tree, *predicted* for this mesh) as JSON.

Usage:
  python -m repro.launch.dryrun --arch llama3.2-3b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod] [--out experiments/dryrun]
"""

import argparse
import dataclasses
import json
import sys
import time
import traceback

import jax
from jax.sharding import PartitionSpec as P

from ..configs import SHAPES, get_config, list_configs
from ..core.analysis import analyze_trace
from ..core.backends.analytical import StepModel, trace_from_step_model
from ..roofline.analysis import build_report, collective_bytes_from_hlo
from ..sharding.act_sharding import activation_sharding, moe_weight_sharding
from ..sharding.partition import (
    batch_pspec,
    cache_pspec,
    fsdp_axes,
    make_sharding_tree,
    param_pspec,
    state_shardings,
)
from .mesh import describe_mesh, make_production_mesh
from .steps import (
    input_specs,
    make_prefill_step,
    make_serve_step,
    make_train_step,
    model_flops,
    serve_params_shapes,
    train_state_shapes,
)


def _in_shardings(cfg, shape, mesh, specs):
    from jax.sharding import NamedSharding

    def batch_shard(tree):
        return jax.tree.map(
            lambda s: NamedSharding(
                mesh, batch_pspec(mesh, s.shape[0], s.ndim)
            ),
            tree,
        )

    if shape.kind == "train":
        state = state_shardings(train_state_shapes(cfg), mesh, cfg)
        return (state, batch_shard(specs[0]))
    params = make_sharding_tree(serve_params_shapes(cfg), mesh, cfg,
                                param_pspec)
    if shape.kind == "prefill":
        return (params, batch_shard(specs[0]))
    token, pos, caches = specs
    cache_sh = make_sharding_tree(caches, mesh, cfg, cache_pspec)
    return (params, batch_shard(token), batch_shard(pos), cache_sh)


def _act_spec(cfg, shape, mesh):
    """Layer-boundary activation sharding: batch over FSDP, sequence over
    the model axis (SP) when divisible. Decode steps (S=1) skip it."""
    if shape.kind == "decode":
        return None
    if shape.seq_len % mesh.shape["model"] != 0:
        return None
    fsdp = fsdp_axes(mesh)
    b_ax = fsdp if shape.global_batch % _axsize(mesh, fsdp) == 0 else None
    return P(b_ax, "model", None)


def _axsize(mesh, axes):
    import numpy as np
    return int(np.prod([mesh.shape[a] for a in axes])) if axes else 1


def _moe_specs(cfg, mesh):
    """Compute-time MoE weight layout (§Perf A5): expert-parallel over
    ``model`` when E divides it, else TP over d_ff; the FSDP d_model dim
    is always gathered."""
    if not cfg.is_moe:
        return (None, None)
    if cfg.moe_experts_physical % mesh.shape["model"] == 0:
        return (P("model", None, None), P("model", None, None))
    if cfg.moe_d_ff % mesh.shape["model"] == 0:
        return (P(None, None, "model"), P(None, "model", None))
    return (P(), P())


def _compile_cell(cfg, shape, mesh):
    """Lower + compile one cell under the mesh; returns timings too."""
    from jax.sharding import NamedSharding

    specs = input_specs(cfg, shape)
    shardings = _in_shardings(cfg, shape, mesh, specs)
    out_shardings = None
    if shape.kind == "train":
        step = make_train_step(cfg)
        args = (train_state_shapes(cfg),) + specs
        donate = (0,)
        out_shardings = (shardings[0], None)  # new state keeps its layout
    elif shape.kind == "prefill":
        step = make_prefill_step(cfg)
        args = (serve_params_shapes(cfg),) + specs
        donate = ()
    else:
        step = make_serve_step(cfg)
        args = (serve_params_shapes(cfg),) + specs
        donate = (3,)
        # logits stay vocab-sharded (sampling is shard-local + argmax
        # exchange, never an all-gather of (B, V)); caches keep their
        # input layout; pos replicated.
        # Iteration B3 (refuted, see EXPERIMENTS.md §Perf): pinning decode
        # output shardings (logits vocab-sharded and/or cache out == in)
        # INCREASED collective bytes — XLA's inferred placements for the
        # donated caches are already copy-free, and forcing layouts makes
        # it reshard the hidden state. Leave decode outputs unpinned.
        out_shardings = None
    t0 = time.time()
    gate_up, down = _moe_specs(cfg, mesh)
    with mesh, activation_sharding(_act_spec(cfg, shape, mesh)), \
            moe_weight_sharding(gate_up, down):
        jitted = jax.jit(step, in_shardings=shardings,
                         out_shardings=out_shardings,
                         donate_argnums=donate)
        lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
    return compiled, t_lower, t_compile


def _cost_triplet(compiled):
    """(flops, hbm bytes, collective-bytes-by-kind) of a compiled module."""
    cost = compiled.cost_analysis()
    stats = collective_bytes_from_hlo(compiled.as_text())
    return (
        float(cost.get("flops", 0.0)),
        float(cost.get("bytes accessed", 0.0)),
        dict(stats.bytes_by_kind),
        dict(stats.count_by_kind),
    )


def _calibrated_cost(cfg, shape, mesh):
    """XLA's cost analysis counts a scan body ONCE regardless of trip
    count (calibrated in tests/test_roofline_calibration.py), so per-cell
    roofline terms come from unrolled R=1 / R=2 compiles extrapolated
    linearly in depth — exact for these homogeneous stacks:
        total(R) = m1 + (R - 1) · (m2 - m1).
    """
    period = len(cfg.pattern)
    r = cfg.repeats
    cfg1 = dataclasses.replace(cfg, num_layers=period, scan_layers=False)
    cfg2 = dataclasses.replace(cfg, num_layers=2 * period, scan_layers=False)
    c1, *_ = _compile_cell(cfg1, shape, mesh)
    f1, b1, coll1, cnt1 = _cost_triplet(c1)
    if r == 1:
        return f1, b1, coll1, cnt1
    c2, *_ = _compile_cell(cfg2, shape, mesh)
    f2, b2, coll2, cnt2 = _cost_triplet(c2)

    def extrap(m1, m2):
        return m1 + (r - 1) * max(0.0, m2 - m1)

    kinds = set(coll1) | set(coll2)
    coll = {k: int(extrap(coll1.get(k, 0), coll2.get(k, 0))) for k in kinds}
    cnt = {k: int(extrap(cnt1.get(k, 0), cnt2.get(k, 0))) for k in kinds}
    return extrap(f1, f2), extrap(b1, b2), coll, cnt


def run_cell(arch: str, shape_name: str, multi_pod: bool = False,
             out_dir: str = None, verbose: bool = True,
             arch_overrides: dict = None, calibrate: bool = True):
    cfg = get_config(arch)
    if arch_overrides:
        cfg = dataclasses.replace(cfg, **arch_overrides)
    shape = SHAPES[shape_name]
    if shape_name == "long_500k" and not cfg.long_context_ok:
        return {
            "arch": arch, "shape": shape_name,
            "status": "skipped",
            "reason": "pure full attention at every layer (DESIGN.md "
                      "long_500k skip policy)",
        }
    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_desc = describe_mesh(mesh)
    chips = mesh.devices.size

    # 1) production compile (scan stack) — the coherence proof + memory
    compiled, t_lower, t_compile = _compile_cell(cfg, shape, mesh)
    try:
        mem = compiled.memory_analysis()
    except Exception:
        mem = None
    raw_flops, raw_bytes, raw_coll, raw_cnt = _cost_triplet(compiled)

    # 2) depth-calibrated roofline terms (single-pod analysis passes;
    #    the multi-pod sweep is the compile-coherence proof only)
    if calibrate:
        flops, hbm_bytes, coll, coll_cnt = _calibrated_cost(cfg, shape, mesh)
    else:
        flops, hbm_bytes, coll, coll_cnt = (
            raw_flops, raw_bytes, raw_coll, raw_cnt
        )

    report = build_report(
        arch=arch, shape=shape_name, mesh_desc=mesh_desc, chips=chips,
        cost={"flops": flops, "bytes accessed": hbm_bytes},
        hlo_text="",
        model_flops_global=model_flops(cfg, shape),
        memory_analysis=mem,
    )
    report.collective_bytes = float(sum(coll.values()))
    report.collective_detail = coll
    report.collective_count = sum(coll_cnt.values())

    # TALP analytical device metrics (paper eqs. 9–12 predicted for this
    # mesh) + the beyond-paper Computational Efficiency branch.
    sm = StepModel(
        flops=report.flops,
        hbm_bytes=report.hbm_bytes,
        collective_bytes=report.collective_bytes,
        model_flops=report.model_flops,
    )
    talp = analyze_trace(
        trace_from_step_model([sm], steps=1),
        computational_efficiency=sm.computational_efficiency,
    )

    result = {
        "status": "ok",
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        **report.to_dict(),
        "raw_scan_cost": {   # uncalibrated (scan body counted once)
            "flops": raw_flops,
            "hbm_bytes": raw_bytes,
            "collective_bytes": raw_coll,
        },
        "memory_analysis": {
            "peak_memory": report.peak_memory,
            "argument_size": report.argument_size,
            "output_size": report.output_size,
            "temp_size": report.temp_size,
        },
        "talp_device": talp.device.as_dict() if talp.device else None,
    }

    if verbose:
        print(f"=== {arch} × {shape_name} × {mesh_desc} ===")
        if mem is not None:
            print(f"memory_analysis: {mem}")
        print(f"calibrated: flops={flops:.3e} hbm_bytes={hbm_bytes:.3e}")
        print(
            f"roofline: compute={report.compute_s*1e3:.3f}ms "
            f"memory={report.memory_s*1e3:.3f}ms "
            f"collective={report.collective_s*1e3:.3f}ms "
            f"dominant={report.dominant} "
            f"fraction={report.roofline_fraction:.3f} "
            f"useful_ratio={report.useful_flop_ratio:.3f}"
        )
        print(f"collectives: {report.collective_detail}")
        sys.stdout.flush()

    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        tag = f"{arch}__{shape_name}__{mesh_desc}".replace("/", "_")
        with open(os.path.join(out_dir, tag + ".json"), "w") as f:
            json.dump(result, f, indent=2)
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list_configs())
    ap.add_argument("--shape", choices=sorted(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true",
                    help="every (arch × shape) cell on this mesh")
    ap.add_argument("--no-calibrate", action="store_true",
                    help="skip the R=1/R=2 depth-calibration compiles")
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()

    cells = []
    if args.all:
        for arch in list_configs():
            for shape in SHAPES:
                cells.append((arch, shape))
    else:
        if not args.arch or not args.shape:
            ap.error("--arch and --shape required unless --all")
        cells = [(args.arch, args.shape)]

    failures = 0
    for arch, shape in cells:
        try:
            res = run_cell(arch, shape, multi_pod=args.multi_pod,
                           out_dir=args.out,
                           calibrate=not args.no_calibrate)
            if res["status"] == "skipped":
                print(f"--- {arch} × {shape}: SKIPPED ({res['reason']})")
        except Exception:
            failures += 1
            print(f"!!! {arch} × {shape}: FAILED")
            traceback.print_exc()
        sys.stdout.flush()
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
