import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512")
# ^ MUST precede any other import (jax locks device count on first init).

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell: jit with production shardings, .lower(**input_specs),
.compile(); print memory_analysis() (proves the per-device footprint) and
cost_analysis() (FLOPs/bytes for the roofline). Failures here are bugs in
the sharding config.

Usage:
  python -m repro.launch.dryrun --arch olmo-1b --shape decode_32k
  python -m repro.launch.dryrun --all [--multi-pod] [--out report.jsonl]
"""
import argparse
import dataclasses
import json
import sys
import time
import traceback

import jax

from repro.configs import ARCH_IDS, SHAPES, get_config
from repro.launch.inputs import build_cell
from repro.launch.mesh import make_production_mesh
from repro.launch.roofline import analyze, report_row, REPORT_HEADER


def run_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
             verbose: bool = True, save_hlo: str | None = None,
             **cell_kw):
    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    chips = mesh.devices.size
    cell = build_cell(arch, shape_name, mesh, **cell_kw)
    argnames = list(cell.kwargs)
    donate = tuple(argnames.index(n) for n in cell.donate)

    def wrapped(*args):
        return cell.fn(**dict(zip(argnames, args)))

    jitted = jax.jit(
        wrapped,
        in_shardings=tuple(cell.in_shardings.get(n) for n in argnames),
        out_shardings=cell.out_shardings,
        donate_argnums=donate)

    t0 = time.time()
    with jax.set_mesh(mesh):
        lowered = jitted.lower(*[cell.kwargs[n] for n in argnames])
        compiled = lowered.compile()
    t1 = time.time()

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    mem_d = {k: getattr(mem, k) for k in
             ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "generated_code_size_in_bytes")} \
        if mem is not None else {}
    cost_d = dict(cost) if cost else {}
    hlo = compiled.as_text()
    if save_hlo:
        with open(save_hlo, "w") as f:
            f.write(hlo)

    rep = analyze(arch, SHAPES[shape_name], mesh_name, chips, cost_d,
                  mem_d, hlo, get_config(arch))
    if verbose:
        print(f"[dryrun] {arch} x {shape_name} x {mesh_name}: "
              f"compile {t1 - t0:.1f}s")
        print(f"  memory_analysis: {json.dumps(mem_d)}")
        print(f"  cost_analysis: flops={cost_d.get('flops', 0):.3e} "
              f"bytes={cost_d.get('bytes accessed', 0):.3e}")
        print(f"  collectives: {rep.collective_breakdown}")
        print(f"  roofline: compute={rep.t_compute:.3e}s "
              f"memory={rep.t_memory:.3e}s "
              f"collective={rep.t_collective:.3e}s "
              f"-> {rep.bottleneck}-bound "
              f"(useful-flops ratio {rep.useful_flops_ratio:.2f}, "
              f"roofline fraction {rep.roofline_fraction:.2f})")
    return rep, mem_d


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list(ARCH_IDS))
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default=None, help="append JSONL reports here")
    ap.add_argument("--save-hlo", default=None)
    ap.add_argument("--variant", default="baseline",
                    choices=["baseline", "opt"],
                    help="decode-step implementation (§Perf)")
    args = ap.parse_args()

    cells = []
    if args.all:
        for a in ARCH_IDS:
            for s in SHAPES:
                cells.append((a, s))
    else:
        if not args.arch or not args.shape:
            ap.error("--arch and --shape required unless --all")
        cells = [(args.arch, args.shape)]

    meshes = [args.multi_pod]
    if args.both_meshes:
        meshes = [False, True]

    print(REPORT_HEADER)
    failures = []
    for multi_pod in meshes:
        for a, s in cells:
            try:
                kw = ({"variant": args.variant}
                      if SHAPES[s].kind == "decode" else {})
                rep, mem_d = run_cell(a, s, multi_pod=multi_pod,
                                      save_hlo=args.save_hlo, **kw)
                print(report_row(rep))
                if args.out:
                    with open(args.out, "a") as f:
                        rec = dataclasses.asdict(rep)
                        rec["memory_analysis"] = mem_d
                        f.write(json.dumps(rec) + "\n")
            except Exception as e:
                failures.append((a, s, multi_pod, repr(e)))
                traceback.print_exc()
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for f in failures:
            print(" ", f)
        sys.exit(1)
    print("\nall cells compiled OK")


if __name__ == "__main__":
    main()
