import os
os.environ["XLA_FLAGS"] = os.environ.get(
    "DRYRUN_XLA_FLAGS", "--xla_force_host_platform_device_count=512"
)

"""Multi-pod dry-run: lower + compile every (arch x shape) cell on the
production meshes and extract memory/cost/collective analyses.

    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-8b \
        --shape train_4k --mesh single --out experiments/dryrun

    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both

Per-cell results land in <out>/<arch>__<shape>__<mesh>.json; failures are
recorded with the exception text (a failing cell is a bug in the sharding
config — the point of the exercise). --all runs each cell in a fresh
subprocess so XLA compile memory is released between cells.
"""  # noqa: E402

import argparse
import json
import subprocess
import sys
import time
import traceback

import jax

from repro.configs import ARCH_IDS, all_cells, get_config
from repro.launch.mesh import make_production_mesh
from repro.launch.roofline import (
    HBM_BW, LINK_BW, PEAK_FLOPS, collective_bytes, model_flops, roofline_terms,
)
from repro.launch.steps import build_step


def run_cell(arch_id: str, shape_name: str, multi_pod: bool, out_dir: str,
             skip_existing: bool = False, overrides: dict = None,
             tag: str = "") -> dict:
    mesh_name = "multi" if multi_pod else "single"
    os.makedirs(out_dir, exist_ok=True)
    suffix = f"__{tag}" if tag else ""
    path = os.path.join(
        out_dir, f"{arch_id}__{shape_name}__{mesh_name}{suffix}.json"
    )
    if skip_existing and os.path.exists(path):
        with open(path) as f:
            prior = json.load(f)
        if prior.get("status") == "ok":
            return prior

    arch = get_config(arch_id)
    sh0 = dict(arch.shapes[shape_name])
    sh0.update(overrides or {})
    import dataclasses as _dc

    arch = _dc.replace(arch, shapes={**arch.shapes, shape_name: sh0})
    record = {
        "arch": arch_id,
        "shape": shape_name,
        "mesh": mesh_name,
        "status": "failed",
    }
    t0 = time.time()
    try:
        mesh = make_production_mesh(multi_pod=multi_pod)
        n_chips = int(mesh.devices.size)

        def compile_once(arch_):
            bundle = build_step(arch_, shape_name, mesh)
            with jax.set_mesh(mesh):
                jitted = jax.jit(
                    bundle.fn,
                    in_shardings=bundle.in_shardings,
                    out_shardings=bundle.out_shardings,
                    donate_argnums=bundle.donate_argnums,
                )
                lowered = jitted.lower(*bundle.abstract_args)
                compiled = lowered.compile()
                mem_ = compiled.memory_analysis()
                cost_ = compiled.cost_analysis()
                hlo_ = compiled.as_text()
            return bundle, mem_, cost_, collective_bytes(hlo_)

        bundle, mem, cost, coll = compile_once(arch)
        t_compile_total = time.time() - t0
        t_lower, t_compile = 0.0, t_compile_total

        flops_dev = float(cost.get("flops", 0.0))
        bytes_dev = float(cost.get("bytes accessed", 0.0))
        coll_dev = float(coll["total_bytes"])

        # lax.scan bodies are cost-analysed ONCE, not x trip count. For LM
        # cells, compile L=1 and L=2 variants and extrapolate the per-layer
        # deltas exactly (all body terms are linear in n_layers). See
        # EXPERIMENTS.md §Roofline methodology.
        extrapolated = False
        if arch.kind == "lm":
            L = arch.model.n_layers
            costs, colls = {}, {}
            for l_small in (1, 2):
                # unroll the (tiny) layer stack so per-layer costs are in
                # the analysed HLO rather than inside a once-counted scan
                arch_s = _dc.replace(
                    arch,
                    model=_dc.replace(arch.model, n_layers=l_small),
                    shapes={
                        **arch.shapes,
                        shape_name: {**arch.shapes[shape_name],
                                     "unroll_layers": True},
                    },
                )
                _, _, cost_s, coll_s = compile_once(arch_s)
                costs[l_small] = cost_s
                colls[l_small] = coll_s

            def extrap(f1: float, f2: float) -> float:
                per_layer = max(f2 - f1, 0.0)
                return f1 + per_layer * (L - 1)

            flops_dev = extrap(
                float(costs[1].get("flops", 0.0)), float(costs[2].get("flops", 0.0))
            )
            bytes_dev = extrap(
                float(costs[1].get("bytes accessed", 0.0)),
                float(costs[2].get("bytes accessed", 0.0)),
            )
            coll_dev = extrap(
                float(colls[1]["total_bytes"]), float(colls[2]["total_bytes"])
            )
            coll = {
                "per_kind_bytes": {
                    k: int(extrap(colls[1]["per_kind_bytes"][k],
                                  colls[2]["per_kind_bytes"][k]))
                    for k in colls[1]["per_kind_bytes"]
                },
                "per_kind_counts": {
                    k: int(extrap(colls[1]["per_kind_counts"][k],
                                  colls[2]["per_kind_counts"][k]))
                    for k in colls[1]["per_kind_counts"]
                },
                "total_bytes": coll_dev,
            }
            extrapolated = True
        terms = roofline_terms(flops_dev, bytes_dev, coll_dev)

        sh = arch.shapes[shape_name]
        if arch.kind == "lm":
            if sh["step"] == "train":
                d = sh["global_batch"] * sh["seq_len"]
                training = True
            elif sh["step"] == "prefill":
                d = sh["global_batch"] * sh["seq_len"]
                training = False
            else:
                d = sh["global_batch"]  # one token per request
                training = False
            useful = model_flops("lm", arch.model, sh, d, training)
        else:
            useful = None

        record.update(
            status="ok",
            description=bundle.description,
            n_chips=n_chips,
            lower_s=round(t_lower, 2),
            compile_s=round(t_compile, 2),
            scan_body_extrapolated=extrapolated,
            overrides=overrides or {},
            memory=dict(
                argument_bytes=int(getattr(mem, "argument_size_in_bytes", 0)),
                output_bytes=int(getattr(mem, "output_size_in_bytes", 0)),
                temp_bytes=int(getattr(mem, "temp_size_in_bytes", 0)),
                code_bytes=int(getattr(mem, "generated_code_size_in_bytes", 0)),
            ),
            cost=dict(
                flops_per_device=flops_dev,
                bytes_per_device=bytes_dev,
                global_flops=flops_dev * n_chips,
            ),
            collectives=coll,
            roofline=terms,
            hw=dict(peak_flops=PEAK_FLOPS, hbm_bw=HBM_BW, link_bw=LINK_BW),
        )
        if useful is not None:
            record["model_flops_global"] = useful
            gf = flops_dev * n_chips
            record["useful_flops_ratio"] = useful / gf if gf else None
    except Exception as e:  # noqa: BLE001
        record["error"] = f"{type(e).__name__}: {e}"
        record["traceback"] = traceback.format_exc()[-4000:]
    record["wall_s"] = round(time.time() - t0, 2)
    with open(path, "w") as f:
        json.dump(record, f, indent=2)
    return record


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape")
    ap.add_argument("--mesh", choices=("single", "multi", "both"), default="single")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--set", action="append", default=[],
                    help="shape override k=v (perf iteration knobs)")
    ap.add_argument("--tag", default="", help="suffix for the output json")
    args = ap.parse_args()

    overrides = {}
    for kv in args.set:
        k, v = kv.split("=", 1)
        try:
            v = json.loads(v)
        except json.JSONDecodeError:
            pass
        overrides[k] = v

    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    if args.all:
        cells = all_cells()
        failures = 0
        for arch_id, shape in cells:
            for m in meshes:
                mesh_name = m
                path = os.path.join(args.out, f"{arch_id}__{shape}__{mesh_name}.json")
                if args.skip_existing and os.path.exists(path):
                    with open(path) as f:
                        rec = json.load(f)
                    if rec.get("status") == "ok":
                        print(f"[skip] {arch_id} x {shape} x {m}: ok")
                        continue
                cmd = [
                    sys.executable, "-m", "repro.launch.dryrun",
                    "--arch", arch_id, "--shape", shape, "--mesh", m,
                    "--out", args.out,
                ]
                r = subprocess.run(cmd, capture_output=True, text=True)
                try:
                    with open(path) as f:
                        rec = json.load(f)
                    ok = rec["status"] == "ok"
                except FileNotFoundError:
                    ok, rec = False, {"error": r.stderr[-500:]}
                failures += 0 if ok else 1
                msg = (
                    f"compile={rec.get('compile_s')}s dom={rec.get('roofline', {}).get('dominant')}"
                    if ok
                    else rec.get("error", "?")[:200]
                )
                print(f"[{'ok' if ok else 'FAIL'}] {arch_id} x {shape} x {m}: {msg}",
                      flush=True)
        print(f"done; {failures} failures")
        return 1 if failures else 0

    assert args.arch and args.shape, "--arch/--shape or --all required"
    for m in meshes:
        rec = run_cell(args.arch, args.shape, m == "multi", args.out,
                       args.skip_existing, overrides, args.tag)
        if rec["status"] == "ok":
            rt = rec["roofline"]
            print(
                f"{args.arch} x {args.shape} x {m}: ok "
                f"compile={rec['compile_s']}s "
                f"compute={rt['compute_s']:.3e}s memory={rt['memory_s']:.3e}s "
                f"collective={rt['collective_s']:.3e}s dominant={rt['dominant']}"
            )
            print("memory:", rec["memory"])
        else:
            print(f"{args.arch} x {args.shape} x {m}: FAILED\n{rec.get('traceback', '')}")
            return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
