import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: .lower().compile() every (arch x shape x mesh) cell.

Proves the distribution config is coherent without hardware:
  * builds the production mesh (16x16 single-pod / 2x16x16 multi-pod),
  * lowers the real step function against ShapeDtypeStruct inputs with the
    production in/out shardings,
  * compiles, records memory_analysis() (fits-in-HBM proof),
    cost_analysis() (FLOPs/bytes) and the collective schedule parsed from
    the optimized HLO (for EXPERIMENTS.md §Roofline).

Usage:
  python -m repro.launch.dryrun --arch olmo-1b --shape train_4k --mesh both
  python -m repro.launch.dryrun --all --mesh single --out results.json
"""
import argparse
import json
import re
import sys
import time
import traceback

import jax
import numpy as np


COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(type_str: str) -> int:
    """Sum bytes over every 'dtype[dims]' in an HLO type string (handles
    tuples)."""
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def parse_collectives(hlo_text: str) -> dict:
    """Per-device collective schedule: op counts + output bytes + estimated
    wire bytes (ring algorithm: all-reduce 2x payload, others ~1x)."""
    stats = {c: dict(count=0, bytes=0) for c in COLLECTIVES}
    for line in hlo_text.splitlines():
        s = line.strip()
        if not s or "=" not in s:
            continue
        m = re.match(r"%?[\w.\-]+\s*=\s*(\([^)]*\)|\S+)\s+([\w\-]+)", s)
        if not m:
            continue
        op = m.group(2)
        # match e.g. all-reduce, all-gather-start, all-reduce-done
        base = None
        for c in COLLECTIVES:
            if op == c or op.startswith(c + "-start"):
                base = c
                break
        if base is None:
            continue
        stats[base]["count"] += 1
        stats[base]["bytes"] += _shape_bytes(m.group(1))
    wire = 0
    for c, st in stats.items():
        factor = 2.0 if c == "all-reduce" else 1.0
        wire += factor * st["bytes"]
    return dict(per_op=stats, wire_bytes_per_device=wire)


def roofline_terms(per_dev_flops, per_dev_bytes, wire_bytes, n_chips,
                   hw=None):
    from .mesh import HW
    hw = hw or HW
    return dict(
        compute_s=per_dev_flops / hw["peak_flops_bf16"],
        memory_s=per_dev_bytes / hw["hbm_bw"],
        collective_s=wire_bytes / hw["ici_bw"],
        n_chips=n_chips,
    )


def _lower_compile(spec, shape_name, mesh):
    from .steps import build_bundle
    bundle = build_bundle(spec, shape_name, mesh)
    # set_mesh installs the sharding context that shard_map and
    # with_sharding_constraint resolve axis names against
    with jax.set_mesh(mesh):
        jitted = jax.jit(bundle.fn,
                         in_shardings=bundle.in_shardings,
                         out_shardings=bundle.out_shardings,
                         donate_argnums=bundle.donate_argnums)
        lowered = jitted.lower(*bundle.args)
        compiled = lowered.compile()
    return compiled


def _cost_of(compiled, skip_hlo=False):
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    flops = float(cost.get("flops", 0.0))
    byts = float(cost.get("bytes accessed", 0.0))
    coll = (dict(per_op={}, wire_bytes_per_device=0.0) if skip_hlo
            else parse_collectives(compiled.as_text()))
    return flops, byts, coll


def measured_cost(spec, shape_name, mesh, skip_hlo=False):
    """Scan-corrected per-device cost: two unrolled reduced-depth variants,
    linear fit in n_layers, extrapolated to the real depth, rescaled by the
    microbatch count (see steps.analysis_variant)."""
    from .steps import analysis_variant
    var = analysis_variant(spec, shape_name, 2, mesh)
    if var is None:  # no scans in this family: real compile is exact
        return None
    cfg_layers = spec.config.n_layers
    pts = []
    for L in (2, 4):
        spec2, shape2, scale = analysis_variant(spec, shape_name, L, mesh)
        comp = _lower_compile(spec2, shape_name, mesh)
        f, b, c = _cost_of(comp, skip_hlo)
        pts.append((L, f, b, c["wire_bytes_per_device"], scale))
    (l1, f1, b1, w1, sc), (l2, f2, b2, w2, _) = pts

    def fit(c1, c2):
        slope = (c2 - c1) / (l2 - l1)
        return max((c1 - slope * l1) + slope * cfg_layers, 0.0)

    return dict(flops=fit(f1, f2) * sc,
                bytes_accessed=fit(b1, b2) * sc,
                wire_bytes=fit(w1, w2) * sc,
                fit_points=[dict(L=p[0], flops=p[1], bytes=p[2],
                                 wire=p[3]) for p in pts],
                microbatch_scale=sc)


def run_cell(arch_id: str, shape_name: str, multi_pod: bool,
             skip_hlo: bool = False) -> dict:
    from ..configs import get_arch
    from .mesh import HW, make_production_mesh

    spec = get_arch(arch_id)
    shape = spec.shapes[shape_name]
    if shape.skip:
        return dict(arch=arch_id, shape=shape_name,
                    mesh="multi" if multi_pod else "single",
                    status="skipped", reason=shape.skip)
    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = int(np.prod(list(mesh.shape.values())))
    t_lower = time.time() - t0
    compiled = _lower_compile(spec, shape_name, mesh)
    t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    mem_info = dict(
        argument_bytes=getattr(mem, "argument_size_in_bytes", None),
        output_bytes=getattr(mem, "output_size_in_bytes", None),
        temp_bytes=getattr(mem, "temp_size_in_bytes", None),
        alias_bytes=getattr(mem, "alias_size_in_bytes", None),
        code_bytes=getattr(mem, "generated_code_size_in_bytes", None),
    )
    live = ((mem_info["argument_bytes"] or 0)
            + (mem_info["output_bytes"] or 0)
            + (mem_info["temp_bytes"] or 0)
            - (mem_info["alias_bytes"] or 0))
    raw_flops, raw_bytes, coll = _cost_of(compiled, skip_hlo)
    # scan-corrected measurement (while bodies count once in XLA's model)
    corr = measured_cost(spec, shape_name, mesh, skip_hlo)
    if corr is not None:
        flops, bytes_accessed = corr["flops"], corr["bytes_accessed"]
        wire = corr["wire_bytes"]
    else:
        flops, bytes_accessed = raw_flops, raw_bytes
        wire = coll["wire_bytes_per_device"]
    terms = roofline_terms(flops, bytes_accessed, wire, n_chips)
    return dict(
        arch=arch_id, shape=shape_name,
        mesh="multi" if multi_pod else "single",
        status="ok", kind=shape.kind,
        n_chips=n_chips,
        lower_s=round(t_lower, 1), compile_s=round(t_compile, 1),
        per_device=dict(flops=flops, bytes_accessed=bytes_accessed,
                        wire_bytes=wire, live_bytes=live,
                        raw_while_once=dict(flops=raw_flops,
                                            bytes=raw_bytes), **mem_info),
        fits_hbm=bool(live <= HW["hbm_bytes"]) if live else None,
        collectives=coll, scan_correction=corr, roofline=terms,
    )


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="both")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=None)
    ap.add_argument("--skip-hlo", action="store_true",
                    help="skip collective parsing (faster)")
    args = ap.parse_args(argv)

    from ..configs import all_cells
    cells = all_cells(include_skipped=True) if args.all else \
        [(args.arch, args.shape)]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    results = []
    for arch_id, shape_name in cells:
        for mp in meshes:
            tag = f"{arch_id}/{shape_name}/{'multi' if mp else 'single'}"
            try:
                r = run_cell(arch_id, shape_name, mp,
                             skip_hlo=args.skip_hlo)
            except Exception as e:  # record failures, keep going
                r = dict(arch=arch_id, shape=shape_name,
                         mesh="multi" if mp else "single",
                         status="error", error=f"{type(e).__name__}: {e}",
                         trace=traceback.format_exc()[-2000:])
            results.append(r)
            status = r["status"]
            extra = ""
            if status == "ok":
                t = r["roofline"]
                extra = (f" flops/dev={r['per_device']['flops']:.3e}"
                         f" live={r['per_device']['live_bytes']/2**30:.2f}GiB"
                         f" comp={t['compute_s']:.4f}s"
                         f" mem={t['memory_s']:.4f}s"
                         f" coll={t['collective_s']:.4f}s")
            elif status == "error":
                extra = " " + r["error"][:200]
            print(f"[dryrun] {tag}: {status}{extra}", flush=True)
            if args.out:
                with open(args.out, "w") as f:
                    json.dump(results, f, indent=1)
    n_err = sum(r["status"] == "error" for r in results)
    print(f"[dryrun] done: {len(results)} cells, {n_err} errors", flush=True)
    return 1 if n_err else 0


if __name__ == "__main__":
    sys.exit(main())
