"""Multi-pod dry-run (deliverable (e)).

For every (architecture x input-shape x mesh) cell: build the step,
``.lower().compile()``, record memory analysis, cost analysis, and the
tier-classified collective-byte parse, then derive the three roofline terms
(EXPERIMENTS.md §Roofline).  One JSON artifact per cell under --out.

The two ``os.environ`` lines below MUST stay the first statements (after
the future import python mandates come first) — jax locks the device count
at first init (see the brief); no jax import may precede them.
"""
from __future__ import annotations

import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import argparse
import gc
import json
import time
import traceback
from typing import Dict, Optional

import jax

from repro.configs.base import SHAPES, get_arch, list_archs, shape_applicable
from repro.core.topology import HardwareSpec, TwoTierTopology
from repro.launch.cells import FSDP_ARCHS, build_cell
from repro.launch.mesh import make_production_mesh
from repro.roofline.analytics import model_cost
from repro.roofline.hlo_parse import parse_collectives


def _memory_dict(compiled) -> Dict[str, float]:
    out = {}
    try:
        ma = compiled.memory_analysis()
        for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                     "temp_size_in_bytes", "alias_size_in_bytes",
                     "generated_code_size_in_bytes"):
            v = getattr(ma, attr, None)
            if v is not None:
                out[attr] = float(v)
        out["repr"] = str(ma)[:500]
    except Exception as e:  # backend may not implement it
        out["error"] = repr(e)
    return out


def run_cell(arch_name: str, shape_name: str, *, multi_pod: bool,
             hw: HardwareSpec, attn_impl: str = "masked",
             codec: Optional[str] = None, sync_strategy: str = "hier_striped",
             zero1: bool = True, microbatches: Optional[int] = None,
             seq_shard: bool = False, moe_groups: int = 1,
             loss_chunk: Optional[int] = None, context_parallel: bool = False,
             embed_tp: bool = True,
             save_hlo: Optional[str] = None) -> Dict:
    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    chips = int(mesh.devices.size)
    chips_per_pod = chips // sizes.get("pod", 1)
    topo = TwoTierTopology(num_pods=sizes.get("pod", 1),
                           pod_shape=(sizes.get("data", 1), sizes.get("model", 1)),
                           hw=hw)
    rec: Dict = {"arch": arch_name, "shape": shape_name,
                 "mesh": list(mesh.devices.shape), "multi_pod": multi_pod,
                 "chips": chips, "attn_impl": attn_impl, "codec": codec,
                 "strategy": sync_strategy, "zero1": zero1,
                 "seq_shard": seq_shard, "moe_groups": moe_groups,
                 "context_parallel": context_parallel, "embed_tp": embed_tp,
                 "microbatches": microbatches, "loss_chunk": loss_chunk}
    try:
        cell = build_cell(arch_name, shape_name, mesh, topo=topo,
                          attn_impl=attn_impl, codec=codec,
                          sync_strategy=sync_strategy, zero1=zero1,
                          microbatches=microbatches, seq_shard=seq_shard,
                          moe_groups=moe_groups, loss_chunk=loss_chunk,
                          context_parallel=context_parallel, embed_tp=embed_tp)
        rec["mode"] = cell.mode
        rec["step_kind"] = cell.step_kind
        lowered = cell.lower()
        t_lower = time.time()
        compiled = lowered.compile()
        t_compile = time.time()
        rec["lower_s"] = round(t_lower - t0, 2)
        rec["compile_s"] = round(t_compile - t_lower, 2)

        rec["memory"] = _memory_dict(compiled)
        try:
            ca = compiled.cost_analysis()
            rec["cost_analysis"] = {k: float(v) for k, v in ca.items()
                                    if isinstance(v, (int, float))
                                    and ("flops" in k or "bytes accessed" == k
                                         or "optimal_seconds" in k)}
        except Exception as e:
            rec["cost_analysis"] = {"error": repr(e)}

        hlo = compiled.as_text()
        rec["hlo_chars"] = len(hlo)
        if save_hlo:
            with open(save_hlo, "w") as f:
                f.write(hlo)
        coll = parse_collectives(hlo, chips_per_pod=chips_per_pod)
        rec["collectives"] = {
            "ici_wire_bytes_per_chip": coll.wire_bytes("ici"),
            "dcn_wire_bytes_per_chip": coll.wire_bytes("dcn"),
            "n_ops_ici": coll.count("ici"),
            "n_ops_dcn": coll.count("dcn"),
            "by_kind": coll.by_kind(),
        }
        del hlo

        # ---- roofline terms --------------------------------------------------
        mc = model_cost(cell.model, cell.shape, cell.mode, n_chips=chips)
        compute_s = mc["flops"] / (chips * hw.peak_flops_bf16)
        memory_s = mc["bytes"] / (chips * hw.hbm_bw)
        ici_s = coll.wire_bytes("ici") / hw.ici_bw
        dcn_s = coll.wire_bytes("dcn") / hw.dcn_bw
        coll_s = ici_s + dcn_s
        terms = {"compute_s": compute_s, "memory_s": memory_s,
                 "ici_s": ici_s, "dcn_s": dcn_s, "collective_s": coll_s}
        dominant = max(terms, key=lambda k: terms[k] if k not in ("ici_s", "dcn_s") else 0)
        bound_s = max(compute_s, memory_s, coll_s)
        rec["roofline"] = {
            **terms,
            "dominant": max([("compute_s", compute_s), ("memory_s", memory_s),
                             ("collective_s", coll_s)], key=lambda kv: kv[1])[0],
            "step_lower_bound_s": bound_s,
            "roofline_fraction": compute_s / bound_s if bound_s > 0 else 0.0,
            "hlo_flops_global": mc["flops"],
            "hlo_bytes_global": mc["bytes"],
            "model_flops": mc["model_flops"],
            "useful_ratio": mc["useful_ratio"],
            "params": mc["params"],
            "active_params": mc["active_params"],
        }
        rec["ok"] = True
    except Exception as e:
        rec["ok"] = False
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-3000:]
    rec["total_s"] = round(time.time() - t0, 2)
    return rec


def main() -> None:
    ap = argparse.ArgumentParser(description="DFabric multi-pod dry-run")
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--attn-impl", default="masked")
    ap.add_argument("--codec", default=None)
    ap.add_argument("--strategy", default="hier_striped")
    ap.add_argument("--no-zero1", action="store_true")
    ap.add_argument("--microbatches", type=int, default=None)
    ap.add_argument("--seq-shard", action="store_true")
    ap.add_argument("--context-parallel", action="store_true")
    ap.add_argument("--no-embed-tp", action="store_true")
    ap.add_argument("--moe-groups", type=int, default=1)
    ap.add_argument("--loss-chunk", type=int, default=None)
    ap.add_argument("--tag", default="")
    ap.add_argument("--save-hlo", default=None)
    args = ap.parse_args()

    os.makedirs(args.out, exist_ok=True)
    archs = list_archs() if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]
    hw = HardwareSpec()

    results = []
    for arch_name in archs:
        for shape_name in shapes:
            ok, why = shape_applicable(get_arch(arch_name), SHAPES[shape_name])
            for multi in meshes:
                tagm = "multi" if multi else "single"
                name = f"{arch_name}__{shape_name}__{tagm}"
                if args.tag:
                    name += f"__{args.tag}"
                path = os.path.join(args.out, name + ".json")
                if not ok:
                    rec = {"arch": arch_name, "shape": shape_name,
                           "multi_pod": multi, "ok": True, "skipped": True,
                           "skip_reason": why}
                    with open(path, "w") as f:
                        json.dump(rec, f, indent=1)
                    print(f"SKIP {name}: {why}")
                    continue
                print(f"RUN  {name} ...", flush=True)
                rec = run_cell(arch_name, shape_name, multi_pod=multi, hw=hw,
                               attn_impl=args.attn_impl, codec=args.codec,
                               sync_strategy=args.strategy,
                               zero1=not args.no_zero1,
                               microbatches=args.microbatches,
                               seq_shard=args.seq_shard,
                               context_parallel=args.context_parallel,
                               embed_tp=not args.no_embed_tp,
                               moe_groups=args.moe_groups,
                               loss_chunk=args.loss_chunk,
                               save_hlo=args.save_hlo)
                with open(path, "w") as f:
                    json.dump(rec, f, indent=1)
                status = "OK" if rec.get("ok") else "FAIL"
                rf = rec.get("roofline", {})
                print(f"{status} {name}  compile={rec.get('compile_s')}s "
                      f"dominant={rf.get('dominant')} "
                      f"frac={rf.get('roofline_fraction', 0):.3f}", flush=True)
                if not rec.get("ok"):
                    print(rec.get("error"))
                results.append(rec)
                gc.collect()
    n_ok = sum(1 for r in results if r.get("ok"))
    print(f"\n{n_ok}/{len(results)} cells OK")
    if n_ok < len(results):
        raise SystemExit(f"{len(results) - n_ok} cell(s) failed")


if __name__ == "__main__":
    main()
