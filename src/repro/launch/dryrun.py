import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any other import (jax locks the device
# count at first init). Everything below is ordinary code.

"""Multi-pod dry-run: lower + compile every (arch x shape) cell on the
production meshes and extract the roofline terms.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch glm4-9b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both \
      --out benchmarks/results/dryrun.json

Per cell it records: compiled memory_analysis (bytes/device), HLO flops &
bytes accessed from cost_analysis (per device), per-collective byte counts
parsed from the post-SPMD HLO (operand sizes, per device), MODEL_FLOPS
metadata, and lower/compile wall times. Failures (sharding mismatch,
unsupported collective) are bugs — the run exits non-zero listing them.
"""
import argparse
import json
import re
import time
import traceback
from typing import Any, Dict

import jax

from repro.configs import registry
from repro.launch import cells as cells_mod
from repro.launch.cache import enable_compile_cache
from repro.launch.mesh import (HBM_BW, HBM_BYTES, ICI_BW, PEAK_FLOPS_BF16,
                               make_production_mesh)

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")

_DTYPE_BYTES = {"f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}

_SHAPE_RE = re.compile(r"(f64|f32|f16|bf16|s64|u64|s32|u32|s16|u16|s8|u8|"
                       r"pred|f8e4m3fn|f8e5m2)\[([0-9,]*)\]")

# Line shape: `%name = <type-or-tuple> <collective>(%operands...), ...`
_COLL_RE = re.compile(r"=\s+(.*?)\s+(" + "|".join(COLLECTIVES) + r")\(")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES[dtype]


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Per-device link traffic from post-SPMD HLO collectives.

    Post-optimization HLO operands carry no inline types, so we read each
    collective's *output* type (tuple types included) and apply the ring
    cost model: all-reduce moves ~2x its size per device (reduce-scatter +
    all-gather phases); all-gather / reduce-scatter / all-to-all /
    collective-permute move ~1x.
    """
    out: Dict[str, int] = {k: 0 for k in COLLECTIVES}
    out["count"] = 0
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        type_str, kind = m.group(1), m.group(2)
        nbytes = sum(_shape_bytes(d, dims)
                     for d, dims in _SHAPE_RE.findall(type_str))
        factor = 2 if kind == "all-reduce" else 1
        out[kind] += factor * nbytes
        out["count"] += 1
    return out


import dataclasses as _dc


def _spec_with_layers(spec, n_layers: int, smoke: bool):
    """Variant of an ArchSpec with unrolled scans and n_layers layers —
    used by the cost pass (XLA cost analysis visits while bodies once, so
    flops/bytes/collectives are extracted from small fully-unrolled
    lowerings and extrapolated linearly in depth). The variant is installed
    as BOTH config and smoke_config so build_cell picks it either way."""
    base = spec.smoke_config if smoke else spec.config
    if spec.family == "lm":
        # cap unrolled attention blocks (q_chunk >= 512) or the unrolled
        # cost lowering explodes at 32k-seq cells; flop counts are
        # q_chunk-invariant
        cfg = _dc.replace(base, n_layers=n_layers, unroll=True,
                          q_chunk=max(base.q_chunk, 512))
    elif spec.family == "colpali":
        bb = _dc.replace(base.encoder.backbone, n_layers=n_layers,
                         unroll=True,
                         q_chunk=max(base.encoder.backbone.q_chunk, 512))
        cfg = _dc.replace(base, encoder=_dc.replace(base.encoder,
                                                    backbone=bb))
    elif spec.family == "recsys":
        cfg = _dc.replace(base, unroll=True)
    else:
        cfg = base
    return _dc.replace(spec, config=cfg, smoke_config=cfg)


def _lower_compile(spec, cell, mesh, smoke):
    built = cells_mod.build_cell(spec, cell, mesh, smoke=smoke)
    if built.in_shardings is None:
        jitted = built.fn              # already jitted (shard_map search)
    else:
        jitted = jax.jit(built.fn, in_shardings=built.in_shardings,
                         out_shardings=built.out_shardings,
                         donate_argnums=built.donate_argnums)
    lowered = jitted.lower(*built.args)
    return built, lowered.compile()


def _raw_metrics(compiled):
    cost = compiled.cost_analysis()
    coll = collective_bytes(compiled.as_text())
    return {"flops": float(cost.get("flops", 0.0)),
            "bytes": float(cost.get("bytes accessed", 0.0)),
            "coll": coll}


def exact_cost_metrics(spec, cell, mesh, smoke: bool) -> Dict[str, Any]:
    """Loop-exact flops/bytes/collective counts.

    LM/ColPali: lower fully-unrolled variants at two depths (L1, L2) and
    extrapolate linearly to the real depth (layers are identical blocks, so
    every per-device count is affine in depth). DIEN: one unrolled
    lowering (seq scan). Others have no scans — production numbers exact.
    """
    fam = spec.family
    if fam in ("lm", "colpali"):
        base_cfg = spec.smoke_config if smoke else spec.config
        bb = base_cfg if fam == "lm" else base_cfg.encoder.backbone
        l_full = bb.n_layers
        step = bb.global_every if bb.attn_chunk > 0 else 1
        l1, l2 = min(step, l_full), min(2 * step, l_full)
        if l1 == l2:                       # shallow smoke config
            _, c = _lower_compile(_spec_with_layers(spec, l1, smoke), cell,
                                  mesh, smoke)
            m = _raw_metrics(c)
            m["source"] = f"unrolled L={l1}"
            return m
        _, c1 = _lower_compile(_spec_with_layers(spec, l1, smoke), cell,
                               mesh, smoke)
        _, c2 = _lower_compile(_spec_with_layers(spec, l2, smoke), cell,
                               mesh, smoke)
        m1, m2 = _raw_metrics(c1), _raw_metrics(c2)

        def extr(a, b):
            return a + (b - a) * (l_full - l1) / (l2 - l1)

        coll = {k: int(extr(m1["coll"][k], m2["coll"][k]))
                for k in m1["coll"]}
        return {"flops": extr(m1["flops"], m2["flops"]),
                "bytes": extr(m1["bytes"], m2["bytes"]),
                "coll": coll,
                "source": f"extrapolated from unrolled L={l1},{l2}"}
    if fam == "recsys" and spec.config.family == "dien":
        _, c = _lower_compile(_spec_with_layers(spec, 0, smoke), cell, mesh,
                              smoke)
        m = _raw_metrics(c)
        m["source"] = "unrolled seq scan"
        return m
    return {"source": "production"}     # no loops: production is exact


def run_cell(arch_id: str, shape_name: str, multi_pod: bool,
             smoke: bool = False, cost_exact: bool = True) -> Dict[str, Any]:
    spec = registry.get(arch_id)
    cell = next(c for c in spec.shapes if c.name == shape_name)
    if cell.skip:
        return {"arch": arch_id, "shape": shape_name,
                "mesh": "multi" if multi_pod else "single",
                "status": "skipped", "reason": cell.skip}
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.devices.size

    with mesh:
        built = cells_mod.build_cell(spec, cell, mesh, smoke=smoke)
        t0 = time.perf_counter()
        if built.in_shardings is None:
            jitted = built.fn          # already jitted (shard_map search)
        else:
            jitted = jax.jit(built.fn,
                             in_shardings=built.in_shardings,
                             out_shardings=built.out_shardings,
                             donate_argnums=built.donate_argnums)
        lowered = jitted.lower(*built.args)
        t_lower = time.perf_counter() - t0
        t0 = time.perf_counter()
        compiled = lowered.compile()
        t_compile = time.perf_counter() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    coll = collective_bytes(hlo)

    flops = float(cost.get("flops", 0.0))
    bytes_acc = float(cost.get("bytes accessed", 0.0))
    cost_source = "production"
    if cost_exact:
        with mesh:
            em = exact_cost_metrics(spec, cell, mesh, smoke)
        if em["source"] != "production":
            flops, bytes_acc, coll = em["flops"], em["bytes"], em["coll"]
            cost_source = em["source"]
    coll_total = sum(v for k, v in coll.items() if k != "count")

    compute_t = flops / PEAK_FLOPS_BF16
    memory_t = bytes_acc / HBM_BW
    coll_t = coll_total / ICI_BW
    model_flops = built.meta.get("model_flops", 0.0)

    per_dev_bytes = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                     + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    rec = {
        "arch": arch_id, "shape": shape_name,
        "mesh": "multi" if multi_pod else "single",
        "chips": n_chips, "status": "ok",
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "cost_source": cost_source,
        "hlo_flops_per_dev": flops,
        "hlo_bytes_per_dev": bytes_acc,
        "collective_bytes_per_dev": coll,
        "mem": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "peak_bytes": per_dev_bytes,
            "fits_16g": bool(per_dev_bytes <= HBM_BYTES),
        },
        "roofline": {
            "compute_s": compute_t,
            "memory_s": memory_t,
            "collective_s": coll_t,
            "dominant": max(
                [("compute", compute_t), ("memory", memory_t),
                 ("collective", coll_t)], key=lambda kv: kv[1])[0],
            "model_flops_total": model_flops,
            "model_flops_per_dev": model_flops / n_chips,
            "useful_flops_ratio": (model_flops / n_chips / flops)
            if flops else 0.0,
            "roofline_frac": ((model_flops / n_chips / PEAK_FLOPS_BF16)
                              / max(compute_t, memory_t, coll_t))
            if max(compute_t, memory_t, coll_t) > 0 else 0.0,
        },
        "meta": {k: v for k, v in built.meta.items()},
    }
    return rec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="use reduced configs (CI sanity)")
    ap.add_argument("--no-cost-exact", action="store_true",
                    help="skip the unrolled cost pass (multi-pod sweeps: "
                         "the roofline table is single-pod only)")
    ap.add_argument("--out", default=None)
    ap.add_argument("--list", action="store_true")
    args = ap.parse_args(argv)

    if args.list:
        for arch_id, cell in registry.all_cells(include_skipped=True):
            flag = f"  [SKIP: {cell.skip}]" if cell.skip else ""
            print(f"{arch_id:28s} {cell.name:16s} {cell.kind:10s}{flag}")
        return 0

    enable_compile_cache()
    todo = []
    meshes = (["single", "multi"] if args.mesh == "both" else [args.mesh])
    if args.all:
        for arch_id, cell in registry.all_cells():
            for m in meshes:
                todo.append((arch_id, cell.name, m == "multi"))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        for m in meshes:
            todo.append((args.arch, args.shape, m == "multi"))

    results, failures = [], []
    for arch_id, shape, multi in todo:
        tag = f"{arch_id}/{shape}/{'multi' if multi else 'single'}"
        print(f"=== {tag} ===", flush=True)
        try:
            rec = run_cell(arch_id, shape, multi, smoke=args.smoke,
                           cost_exact=not args.no_cost_exact)
            results.append(rec)
            if rec["status"] == "ok":
                r = rec["roofline"]
                print(f"  ok: compile {rec['compile_s']}s | "
                      f"mem/dev {rec['mem']['peak_bytes']/2**30:.2f} GiB "
                      f"(fits16G={rec['mem']['fits_16g']}) | "
                      f"compute {r['compute_s']:.2e}s "
                      f"memory {r['memory_s']:.2e}s "
                      f"collective {r['collective_s']:.2e}s "
                      f"-> {r['dominant']}-bound | "
                      f"roofline_frac {r['roofline_frac']:.3f}", flush=True)
            else:
                print(f"  skipped: {rec['reason']}", flush=True)
        except Exception as e:  # noqa: BLE001 — report and continue
            failures.append((tag, repr(e)))
            traceback.print_exc()
            results.append({"arch": arch_id, "shape": shape,
                            "mesh": "multi" if multi else "single",
                            "status": "error", "error": repr(e)})

    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)
        print(f"wrote {args.out}")

    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for tag, err in failures:
            print(f"  {tag}: {err}")
        return 1
    print(f"\nall {len(results)} cells ok")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
