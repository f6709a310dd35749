import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run driver (the two lines above MUST precede any jax import:
jax locks the device count on first init).

For every (architecture × input-shape × mesh) cell this lowers + compiles the
appropriate step function (train_step / prefill_step / serve_step) against
ShapeDtypeStruct inputs, prints memory_analysis() and cost_analysis(), parses
collective bytes out of the compiled HLO, and writes a JSON record to
experiments/dryrun/.

Usage:
    python -m repro.launch.dryrun --arch qwen2-7b --shape train_4k
    python -m repro.launch.dryrun --all [--mesh single|multi|both]
    python -m repro.launch.dryrun --all --skip-done     # resume a sweep
"""

import argparse       # noqa: E402
import json           # noqa: E402
import time           # noqa: E402
import traceback      # noqa: E402
from pathlib import Path  # noqa: E402

import jax            # noqa: E402

from ..configs import SHAPES, cell_is_applicable, get_arch  # noqa: E402
from ..models.transformer import get_model                  # noqa: E402
from ..roofline.analysis import (RooflineTerms,  # noqa: E402
                                 count_params, model_flops)
from ..roofline.hlo_walk import analyze as hlo_analyze      # noqa: E402
from . import sharding as shp   # noqa: E402
from . import specs             # noqa: E402
from .mesh import make_production_mesh  # noqa: E402
from .steps import make_decode_step, make_prefill_step, make_train_step  # noqa: E402

OUT_DIR = Path(__file__).resolve().parents[3] / "experiments" / "dryrun"


def _sharding_tree(tree, fn):
    return jax.tree_util.tree_map_with_path(fn, tree)


# perf-variant presets (EXPERIMENTS.md §Perf): dataclasses.replace overrides
VARIANTS = {
    "seqpar": {"seq_parallel_kv": True},
    "moecap": {"moe_buffer_shard": "capacity"},
    "seqpar_moecap": {"seq_parallel_kv": True, "moe_buffer_shard": "capacity"},
    "nomicro": {},          # handled via n_micro override below
    "noremat": {"remat": False},
    "moecap_noremat": {"moe_buffer_shard": "capacity", "remat": False},
    "moecap_cf1": {"moe_buffer_shard": "capacity",
                   "moe_capacity_factor": 1.0},
    "kvq8": {"kv_quant_int8": True},
    "moecap2d_cf1": {"moe_buffer_shard": "capacity2d",
                     "moe_capacity_factor": 1.0},
    "moelocal_cf1": {"moe_buffer_shard": "local",
                     "moe_capacity_factor": 1.0},
    "seqpar_kvq8": {"seq_parallel_kv": True, "kv_quant_int8": True},
}


def build_lowered(arch: str, shape: str, multi_pod: bool,
                  variant: str | None = None, n_micro: int | None = None):
    """Lower the cell's step function under the production mesh."""
    import dataclasses
    cfg = get_arch(arch)
    if variant:
        cfg = dataclasses.replace(cfg, **VARIANTS[variant])
    api = get_model(cfg)
    seq, gbatch, kind = SHAPES[shape]
    mesh = make_production_mesh(multi_pod=multi_pod)

    p_sds = specs.param_specs(api)
    p_sh = shp.param_shardings(p_sds, cfg, mesh)
    batch_sds = specs.input_specs(arch, shape)
    batch_sh = jax.tree.map(
        lambda s: shp.data_sharding(s.shape, mesh), batch_sds)

    with shp.activate(mesh):
        if kind == "train":
            if n_micro is None:
                n_micro = specs.n_microbatches(cfg, shape)
            opt_sds = specs.opt_specs(p_sds)
            opt_sh = shp.param_shardings(opt_sds, cfg, mesh)
            step = make_train_step(api, n_micro,
                                   param_dtype=specs.PARAM_DTYPE,
                                   grad_shardings=p_sh)
            jitted = jax.jit(step,
                             in_shardings=(p_sh, opt_sh, batch_sh),
                             out_shardings=(p_sh, opt_sh, None))
            lowered = jitted.lower(p_sds, opt_sds, batch_sds)
        elif kind == "prefill":
            step = make_prefill_step(api, max_len=seq)
            jitted = jax.jit(step, in_shardings=(p_sh, batch_sh))
            lowered = jitted.lower(p_sds, batch_sds)
        else:  # decode
            cache_sds = specs.cache_specs(api, arch, shape)
            cache_sh = shp.cache_shardings(cache_sds, cfg, mesh)
            step = make_decode_step(api)
            # cache buffers are donated: the standing KV/state cache updates
            # in place across serve steps (no functional copy per token)
            jitted = jax.jit(step,
                             in_shardings=(p_sh, cache_sh,
                                           batch_sh["tokens"]),
                             out_shardings=(None, cache_sh, None),
                             donate_argnums=(1,))
            lowered = jitted.lower(p_sds, cache_sds, batch_sds["tokens"])
    return lowered, mesh, cfg, (seq, gbatch, kind)


def run_cell(arch: str, shape: str, mesh_kind: str,
             variant: str | None = None, n_micro: int | None = None) -> dict:
    multi_pod = mesh_kind == "multi"
    n_chips = 512 if multi_pod else 256
    cfg = get_arch(arch)
    ok, why = cell_is_applicable(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape, "mesh": mesh_kind,
                "skipped": why}

    t0 = time.time()
    lowered, mesh, cfg, (seq, gbatch, kind) = build_lowered(
        arch, shape, multi_pod, variant=variant, n_micro=n_micro)
    t_lower = time.time() - t0

    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    # trip-count-aware accounting (cost_analysis counts while bodies once)
    acc = hlo_analyze(hlo)
    flops_dev = acc.flops
    bytes_dev = acc.hbm_bytes
    coll_total = acc.collective_wire_bytes
    terms = RooflineTerms(flops_per_device=flops_dev,
                          bytes_per_device=bytes_dev,
                          collective_per_device=coll_total,
                          n_chips=n_chips)

    n_tokens = gbatch * (seq if kind != "decode" else 1)
    mflops = model_flops(cfg, kind, n_tokens)
    hlo_flops_global = flops_dev * n_chips
    useful = mflops / hlo_flops_global if hlo_flops_global else 0.0

    mem_rec = {}
    if mem is not None:
        for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                     "temp_size_in_bytes", "generated_code_size_in_bytes",
                     "alias_size_in_bytes"):
            mem_rec[attr] = getattr(mem, attr, None)

    rec = {
        "arch": arch, "shape": shape, "mesh": mesh_kind, "chips": n_chips,
        "variant": variant, "n_micro_override": n_micro,
        "kind": kind, "seq": seq, "global_batch": gbatch,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "flops_per_device": flops_dev,
        "bytes_per_device": bytes_dev,
        "collective_bytes_per_device": coll_total,
        "collectives": acc.collective_operand_bytes,
        "collective_counts": acc.collective_counts,
        "cost_analysis_flops_once": float(cost.get("flops", 0.0)),
        "cost_analysis_bytes_once": float(cost.get("bytes accessed", 0.0)),
        "memory_analysis": mem_rec,
        "roofline": terms.to_dict(),
        "model_flops": mflops,
        "model_params_active": count_params(cfg, active_only=True),
        "useful_flops_fraction": useful,
    }
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", default="both", choices=["single", "multi",
                                                       "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--skip-done", action="store_true")
    ap.add_argument("--variant", default=None,
                    help="perf-variant preset (see VARIANTS)")
    ap.add_argument("--n-micro", type=int, default=None)
    ap.add_argument("--out", default=str(OUT_DIR))
    args = ap.parse_args()

    out_dir = Path(args.out)
    out_dir.mkdir(parents=True, exist_ok=True)

    if args.all:
        from ..configs import all_cells
        cells = all_cells()
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells = [(args.arch, args.shape)]
    meshes = {"single": ["single"], "multi": ["multi"],
              "both": ["single", "multi"]}[args.mesh]

    failures = 0
    for arch, shape in cells:
        for mesh_kind in meshes:
            tag = f"{arch}_{shape}_{mesh_kind}".replace(".", "_")
            if args.variant:
                tag += f"__{args.variant}"
            if args.n_micro is not None:
                tag += f"__m{args.n_micro}"
            path = out_dir / f"{tag}.json"
            if args.skip_done and path.exists():
                rec = json.loads(path.read_text())
                if "error" not in rec:
                    print(f"[skip] {tag}")
                    continue
            print(f"[run ] {tag} ...", flush=True)
            try:
                rec = run_cell(arch, shape, mesh_kind,
                               variant=args.variant, n_micro=args.n_micro)
            except Exception as e:  # record failures for triage
                traceback.print_exc()
                rec = {"arch": arch, "shape": shape, "mesh": mesh_kind,
                       "error": f"{type(e).__name__}: {e}"}
                failures += 1
            path.write_text(json.dumps(rec, indent=2, default=str))
            if "skipped" in rec:
                print(f"[skip] {tag}: {rec['skipped']}")
            elif "error" in rec:
                print(f"[FAIL] {tag}: {rec['error'][:200]}")
            else:
                r = rec["roofline"]
                print(f"[ ok ] {tag}: compile {rec['compile_s']}s  "
                      f"flops/dev {rec['flops_per_device']:.3g}  "
                      f"coll/dev {rec['collective_bytes_per_device']:.3g}  "
                      f"dominant={r['dominant']}", flush=True)
    print(f"done; failures={failures}")
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
