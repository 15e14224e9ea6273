import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any other import (jax locks the device
# count at first init).  512 host devices back both production meshes:
# single-pod (16,16) uses the first 256; multi-pod (2,16,16) uses all 512.

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell:  jit(step).lower(**ShapeDtypeStructs).compile()  must succeed;
we record memory_analysis (proves it fits), cost_analysis, and the exact
roofline terms from the trip-count-aware HLO walker (hlo_analysis).

Results go to experiments/dryrun/<arch>__<shape>__<mesh>.json and feed
EXPERIMENTS.md §Dry-run and §Roofline.

Usage:
  python -m repro.launch.dryrun --arch smollm-135m --shape train_4k --mesh single
  python -m repro.launch.dryrun --all --mesh both
"""
import argparse
import json
import time
import traceback

import jax

# v5e-like hardware constants (assignment-provided)
PEAK_FLOPS = 197e12          # bf16 / chip
HBM_BW = 819e9               # B/s / chip
ICI_BW = 50e9                # B/s / link


def set_perf(mode: str):
    """'off' (paper-faithful baseline), 'on', or comma list of flags."""
    from repro import perf
    if mode == "on":
        perf.set_flags(**{k: True for k in ("bf16_attn_io", "rwkv_chunked",
                                            "bf16_moe_dispatch",
                                            "windowed_local_cache")})
    elif mode == "off":
        perf.set_flags(**{k: False for k in ("bf16_attn_io", "rwkv_chunked",
                                             "bf16_moe_dispatch",
                                             "windowed_local_cache")})
    else:
        set_perf("off")
        perf.set_flags(**{k.strip(): True for k in mode.split(",") if k})


def lower_cell(arch: str, shape_name: str, multi_pod: bool,
               optimizer: str | None = None):
    """Lower + compile one cell; returns the result record."""
    from repro.configs import get_config, get_shape
    from repro.launch import steps as steps_mod
    from repro.launch.hlo_analysis import analyze
    from repro.launch.mesh import make_production_mesh, mesh_num_devices

    cfg = get_config(arch)
    shape = get_shape(shape_name)
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = mesh_num_devices(mesh)

    # default optimizer: adafactor for the 400B MoE (memory), adamw otherwise
    if optimizer is None:
        optimizer = "adafactor" if cfg.param_count() > 1e11 else "adamw"

    t0 = time.time()
    with jax.set_mesh(mesh):
        if shape.kind == "train":
            mk = steps_mod.make_train_step(cfg, mesh, optimizer_name=optimizer)
            batch_struct = steps_mod.input_specs(cfg, shape)
            state_struct = jax.eval_shape(mk["make_init"](jax.random.PRNGKey(0)))
            jitted = mk["jit"](batch_struct)
            lowered = jitted.lower(state_struct, batch_struct)
        elif shape.kind == "prefill":
            mk = steps_mod.make_prefill(cfg, mesh, max_seq=shape.seq_len)
            batch_struct = steps_mod.input_specs(cfg, shape)
            p_struct = steps_mod.param_specs(cfg)
            jitted = mk["jit"](batch_struct)
            lowered = jitted.lower(p_struct, batch_struct)
        else:  # decode
            mk = steps_mod.make_decode_step(cfg, mesh, max_seq=shape.seq_len,
                                            batch_size=shape.global_batch)
            batch_struct = steps_mod.input_specs(cfg, shape)
            p_struct = steps_mod.param_specs(cfg)
            jitted = mk["jit"](batch_struct)
            lowered = jitted.lower(p_struct, mk["cache_struct"], batch_struct)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    ca = compiled.cost_analysis() or {}
    hlo = analyze(compiled.as_text())

    # roofline terms (per chip; hlo numbers are per-device post-SPMD)
    compute_s = hlo.flops / PEAK_FLOPS
    memory_s = hlo.bytes / HBM_BW
    collective_s = hlo.collective_bytes / ICI_BW

    n_params = cfg.param_count()
    n_active = cfg.active_param_count()
    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode"
                                   else 1)
    # MODEL_FLOPS: 6*N*D for a train step; 2*N*D forward-only (prefill/decode)
    mf = (6 if shape.kind == "train" else 2) * n_active * tokens

    rec = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "kind": shape.kind, "n_devices": n_dev, "optimizer": optimizer,
        "seq_len": shape.seq_len, "global_batch": shape.global_batch,
        "params": n_params, "active_params": n_active,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "peak_bytes": (mem.argument_size_in_bytes
                           + mem.temp_size_in_bytes),
        },
        "cost_analysis": {k: ca.get(k) for k in ("flops", "bytes accessed")
                          if k in ca},
        "hlo": {
            "flops_per_dev": hlo.flops,
            "bytes_per_dev": hlo.bytes,
            "collective_bytes_per_dev": hlo.collective_bytes,
            "collectives": hlo.collectives,
        },
        "roofline": {
            "compute_s": compute_s,
            "memory_s": memory_s,
            "collective_s": collective_s,
            "dominant": max((("compute", compute_s), ("memory", memory_s),
                             ("collective", collective_s)),
                            key=lambda kv: kv[1])[0],
            "model_flops": mf,
            "hlo_flops_total": hlo.flops * n_dev,
            "useful_ratio": mf / (hlo.flops * n_dev) if hlo.flops else 0.0,
        },
    }
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--optimizer", default=None)
    ap.add_argument("--perf", default="off",
                    help="'off' (paper-faithful baseline), 'on', or a comma "
                         "list of perf flags to enable")
    args = ap.parse_args()
    set_perf(args.perf)

    from repro.configs import ARCHS, SHAPES

    cells = []
    archs = list(ARCHS) if (args.all or args.arch is None) else [args.arch]
    shapes = list(SHAPES) if (args.all or args.shape is None) else [args.shape]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    for a in archs:
        for s in shapes:
            for mp in meshes:
                cells.append((a, s, mp))

    os.makedirs(args.out, exist_ok=True)
    failures = 0
    for a, s, mp in cells:
        tag = f"{a}__{s}__{'multi' if mp else 'single'}"
        path = os.path.join(args.out, tag + ".json")
        try:
            rec = lower_cell(a, s, mp, optimizer=args.optimizer)
            with open(path, "w") as f:
                json.dump(rec, f, indent=1)
            r = rec["roofline"]
            print(f"OK   {tag:60s} compile={rec['compile_s']:6.1f}s "
                  f"peak={rec['memory']['peak_bytes']/2**30:7.2f}GiB/dev "
                  f"dom={r['dominant']:10s} "
                  f"c/m/x={r['compute_s']*1e3:.1f}/{r['memory_s']*1e3:.1f}/"
                  f"{r['collective_s']*1e3:.1f}ms", flush=True)
        except Exception as e:  # noqa: BLE001 — report, continue, fail at end
            failures += 1
            print(f"FAIL {tag}: {e}", flush=True)
            traceback.print_exc()
    if failures:
        raise SystemExit(f"{failures} dry-run cells failed")


if __name__ == "__main__":
    main()
