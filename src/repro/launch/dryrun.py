import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)
# ^ MUST precede every other import (jax locks the device count on first
# init).  The dry-run — and ONLY the dry-run — sees 512 placeholder
# devices so the production meshes can be built on this 1-CPU container.

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this proves the distribution config is coherent without real
hardware:  ``jax.jit(step).lower(**input_specs).compile()`` must succeed on
the 16x16 single-pod mesh AND the 2x16x16 multi-pod mesh; the compiled
artifact yields memory_analysis (fits?), cost_analysis (FLOPs/bytes for
the roofline) and the HLO collective schedule (collective bytes).

Usage:
    python -m repro.launch.dryrun --arch deepseek-moe-16b --shape decode_32k
    python -m repro.launch.dryrun --all --mesh both
    python -m repro.launch.dryrun --all --mesh pod --archs-file cells.txt

Results are cached as JSON under experiments/dryrun/ (one file per cell);
--force recompiles.
"""
import argparse
import json
import time
import traceback

import jax
from jax.sharding import NamedSharding

from repro.compat import cost_analysis
from repro.configs import ASSIGNED, REGISTRY, SHAPES, get_config, shape_applies
from repro.distributed.sharding import ShardingRules
from repro.launch import hlo_analysis
from repro.launch import roofline as rl
from repro.launch.mesh import data_axes, make_production_mesh
from repro.launch.steps import (input_specs, make_prefill_step,
                                make_serve_step, make_train_step)
from repro.models import Parallel

OUT_DIR_DEFAULT = "experiments/dryrun"
#: The production meshes are TPU v5e pods; their roofline uses its peaks.
TARGET_DEVICE_KIND = "TPU v5 lite"


def _named(mesh, spec_tree):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda s: isinstance(s, jax.sharding.PartitionSpec),
    )


def _mem_dict(mem) -> dict:
    fields = ("generated_code_size_in_bytes", "argument_size_in_bytes",
              "output_size_in_bytes", "alias_size_in_bytes",
              "temp_size_in_bytes")
    return {f: int(getattr(mem, f, -1)) for f in fields}


def _cost_dict(compiled) -> dict:
    cost = cost_analysis(compiled)
    return {k: float(v) for k, v in cost.items()
            if isinstance(v, (int, float)) and not k.startswith("utilization")}


def _parse_overrides(pairs) -> dict:
    out = {}
    for kv in pairs or []:
        k, v = kv.split("=", 1)
        if v in ("true", "True"):
            out[k] = True
        elif v in ("false", "False"):
            out[k] = False
        else:
            try:
                out[k] = int(v)
            except ValueError:
                try:
                    out[k] = float(v)
                except ValueError:
                    out[k] = v
    return out


def lower_cell(arch: str, shape_name: str, multi_pod: bool,
               overrides: dict | None = None):
    """Build the jitted step for one cell and lower it.  Returns
    (lowered, mesh, n_devices, cfg, shape)."""
    import dataclasses
    cfg = get_config(arch)
    overrides = dict(overrides or {})
    zero1 = overrides.pop("zero1", False)    # sharding-level, not ModelConfig
    micro = overrides.pop("micro", 1)        # gradient-accumulation slices
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    daxes = data_axes(mesh)
    par = Parallel(mesh=mesh, data_axes=daxes)
    rules = ShardingRules(cfg, mesh, data_axes=daxes, zero_opt=zero1)
    specs = input_specs(cfg, shape)

    p_sh = _named(mesh, rules.param_specs(specs["params"]))
    with mesh:
        if shape.kind == "train":
            step = make_train_step(cfg, par, micro_batches=micro)
            o_sh = _named(mesh, rules.opt_state_specs(specs["opt_state"],
                                                      rules.param_specs(specs["params"])))
            b_sh = _named(mesh, rules.batch_spec(specs["batch"]))
            jitted = jax.jit(
                step,
                in_shardings=(p_sh, o_sh, b_sh),
                out_shardings=(p_sh, o_sh, None),
                donate_argnums=(0, 1),
            )
            lowered = jitted.lower(specs["params"], specs["opt_state"],
                                   specs["batch"])
        elif shape.kind == "prefill":
            step = make_prefill_step(cfg, par, max_len=shape.seq_len)
            b_sh = _named(mesh, rules.batch_spec(specs["batch"]))
            jitted = jax.jit(step, in_shardings=(p_sh, b_sh))
            lowered = jitted.lower(specs["params"], specs["batch"])
        else:  # decode
            step = make_serve_step(cfg, par)
            c_sh = _named(mesh, rules.cache_specs(specs["cache"]))
            tok_sh = (None if specs["tokens"] is None
                      else _named(mesh, rules.batch_spec(specs["tokens"])))
            embeds = specs.get("embeds")
            emb_sh = (None if embeds is None
                      else _named(mesh, rules.batch_spec(embeds)))
            args = [specs["params"], specs["cache"], specs["tokens"],
                    specs["pos"], embeds]
            jitted = jax.jit(
                step,
                in_shardings=(p_sh, c_sh, tok_sh, None, emb_sh),
                out_shardings=(None, None, c_sh),
                donate_argnums=(1,),
            )
            lowered = jitted.lower(*args)
    return lowered, mesh, cfg, shape


def run_cell(arch: str, shape_name: str, multi_pod: bool, out_dir: str,
             force: bool = False, overrides: dict | None = None,
             tag: str = "") -> dict:
    mesh_name = "multipod_2x16x16" if multi_pod else "pod_16x16"
    cell_id = f"{arch}__{shape_name}__{mesh_name}"
    if tag:
        cell_id += f"__{tag}"
    path = os.path.join(out_dir, cell_id + ".json")
    if os.path.exists(path) and not force:
        with open(path) as f:
            return json.load(f)

    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, reason = shape_applies(cfg, shape)
    record = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "kind": shape.kind, "seq_len": shape.seq_len,
        "global_batch": shape.global_batch,
        "overrides": overrides or {}, "tag": tag,
    }
    if not ok:
        record["status"] = "skip"
        record["reason"] = reason
        _write(path, record)
        return record

    t0 = time.time()
    try:
        lowered, mesh, cfg, shape = lower_cell(arch, shape_name, multi_pod,
                                               overrides)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
        cost = _cost_dict(compiled)
        mem = _mem_dict(compiled.memory_analysis())
        hlo = compiled.as_text()
        # loop-aware walk: multiplies scan-body costs by trip counts, which
        # raw cost_analysis does not (see hlo_analysis.py docstring)
        hcost = hlo_analysis.analyze(hlo, mesh.size)
        roof = rl.derive_from_hlo_cost(hcost, mesh.size,
                                       rl.model_flops(cfg, shape),
                                       TARGET_DEVICE_KIND)
        record.update({
            "status": "ok",
            "n_devices": mesh.size,
            "lower_s": round(t_lower, 2),
            "compile_s": round(t_compile, 2),
            "raw_cost_analysis": cost,
            "memory_analysis": mem,
            "hlo_cost": hcost.asdict(),
            "roofline": roof.asdict(),
        })
        print(f"[OK] {cell_id}: dominant={roof.dominant} "
              f"compute={roof.compute_s:.4f}s memory={roof.memory_s:.4f}s "
              f"collective={roof.collective_s:.4f}s "
              f"frac={roof.roofline_fraction:.3f} "
              f"(lower {t_lower:.1f}s compile {t_compile:.1f}s)")
    except Exception as e:  # a failure here is a bug in the system
        record["status"] = "fail"
        record["error"] = f"{type(e).__name__}: {e}"
        record["traceback"] = traceback.format_exc()[-4000:]
        print(f"[FAIL] {cell_id}: {type(e).__name__}: {e}")
    _write(path, record)
    return record


def calibrate_hook(record: dict) -> None:
    """Fold a compiled cell's roofline terms into the arch's committed
    service-time calibration table (repro.core.calibration), if one
    exists.  The attached per-chip FLOPs/bytes cross-check the table's
    analytic energy accounting against the real compiled HLO."""
    if record.get("status") != "ok" or "roofline" not in record:
        return
    from repro.core import calibration as cal
    name = record["arch"]
    try:
        table = cal.load_table(name)
    except FileNotFoundError:
        print(f"[calibrate] no committed service table for {name}; run "
              "benchmarks/bench_calibration.py --refresh first")
        return
    table = cal.attach_dryrun(table, record)
    path = cal.save_table(table)
    print(f"[calibrate] attached {record['shape']} roofline to {path}")


def _write(path: str, record: dict) -> None:
    os.makedirs(os.path.dirname(path), exist_ok=True)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(record, f, indent=1)
    os.replace(tmp, path)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--mesh", default="both", choices=["pod", "multipod", "both"])
    ap.add_argument("--all", action="store_true",
                    help="every assigned (arch x shape) cell")
    ap.add_argument("--include-paper-model", action="store_true")
    ap.add_argument("--out", default=OUT_DIR_DEFAULT)
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--set", nargs="*", default=[], dest="overrides",
                    help="ModelConfig overrides k=v (perf variants)")
    ap.add_argument("--tag", default="", help="artifact suffix for variants")
    ap.add_argument("--calibrate", action="store_true",
                    help="attach each OK cell's roofline terms to the "
                         "arch's committed service-time calibration table")
    args = ap.parse_args()
    overrides = _parse_overrides(args.overrides)

    archs = list(ASSIGNED)
    if args.include_paper_model:
        archs = list(REGISTRY)
    if args.arch:
        archs = [args.arch]
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = {"pod": [False], "multipod": [True], "both": [False, True]}[args.mesh]

    if not (args.all or args.arch):
        ap.error("pass --all or --arch")

    n_fail = 0
    for arch in archs:
        for shape_name in shapes:
            for multi_pod in meshes:
                rec = run_cell(arch, shape_name, multi_pod, args.out,
                               force=args.force, overrides=overrides,
                               tag=args.tag)
                if args.calibrate:
                    calibrate_hook(rec)
                n_fail += rec.get("status") == "fail"
    print(f"done; {n_fail} failures")
    raise SystemExit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
