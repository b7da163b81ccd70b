import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512")
# a CPU-only compile tool: pin the platform so neither this process nor
# its per-cell children (which inherit the environment) ever takes a chip
os.environ["JAX_PLATFORMS"] = "cpu"
# The lines above MUST run before any other import (jax locks the device
# count and platform at first backend init) — see the multi-pod dry-run
# spec.

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell this driver:
  1. builds the production mesh (16×16 single-pod, 2×16×16 multi-pod),
  2. eval_shape's the model init + optimizer + decode states (ShapeDtype
     stand-ins only — no device allocation anywhere),
  3. jits the train/prefill/serve step with explicit in/out shardings,
  4. ``.lower().compile()`` — success proves the sharding config is
     coherent (no mismatched collectives, fits memory at compile),
  5. records ``memory_analysis()`` / ``cost_analysis()`` / the collective
     bytes parsed from the partitioned HLO into a JSON artifact under
     ``experiments/dryrun/`` for the roofline table (§Roofline).

Usage:
  python -m repro.launch.dryrun --arch smollm-135m --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod-only-smoke]
  python -m repro.launch.dryrun --list
"""

import argparse
import json
import re
import sys
import time
import traceback

COLLECTIVE_OPS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                  "collective-permute")

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1,
}

_SHAPE_RE = re.compile(r"\b(f64|f32|f16|bf16|f8e4m3fn|f8e5m2|s64|u64|s32|u32|"
                       r"s16|u16|s8|u8|pred)\[([0-9,]*)\]")

# `%x = f32[32,64]{1,0} all-reduce(%dot), ... replica_groups=[2,4]<=[8]`
_INSTR_RE = re.compile(
    r"=\s+(?P<result>\(?[a-z0-9]+\[[0-9,]*\][^ ]*(?:,\s*[a-z0-9]+\[[0-9,]*\]"
    r"[^ )]*)*\)?)\s+(?P<kind>all-gather|all-reduce|reduce-scatter|"
    r"all-to-all|collective-permute)(?P<start>-start)?\(")
_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _shape_bytes(m) -> int:
    dt, dims = m.group(1), m.group(2)
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES[dt]


def parse_collective_bytes(hlo_text: str) -> dict:
    """Per-chip wire bytes per collective kind, from the partitioned HLO.

    Shapes in the partitioned module are per-device; the RESULT shape is
    used with the ring-algorithm wire factor for a group of size g:
      all-gather         r·(g−1)/g      (receives everyone else's shard)
      all-reduce         2·r·(g−1)/g    (reduce-scatter + all-gather)
      reduce-scatter     r·(g−1)        (result r is the scattered shard)
      all-to-all         r·(g−1)/g
      collective-permute r              (one hop)
    Async -start ops are counted; -done ops carry no new transfer.
    """
    per_kind = {k: 0.0 for k in COLLECTIVE_OPS}
    counts = {k: 0 for k in COLLECTIVE_OPS}
    for line in hlo_text.splitlines():
        m = _INSTR_RE.search(line)
        if not m:
            continue
        kind = m.group("kind")
        r_bytes = sum(_shape_bytes(sm)
                      for sm in _SHAPE_RE.finditer(m.group("result")))
        gm = _GROUPS_RE.search(line)
        g = int(gm.group(2)) if gm else 2
        if g <= 1:
            continue
        if kind == "all-gather":
            wire = r_bytes * (g - 1) / g
        elif kind == "all-reduce":
            wire = 2.0 * r_bytes * (g - 1) / g
        elif kind == "reduce-scatter":
            wire = float(r_bytes) * (g - 1)
        elif kind == "all-to-all":
            wire = r_bytes * (g - 1) / g
        else:  # collective-permute
            wire = float(r_bytes)
        per_kind[kind] += wire
        counts[kind] += 1
    total = sum(per_kind.values())
    return {"per_kind_bytes": {k: int(v) for k, v in per_kind.items()},
            "counts": counts, "total_bytes_per_chip": int(total)}


def _lower_one(cfg, shape, mesh, opts):
    """Lower + compile one step function. Returns (compiled, timings)."""
    import jax

    from repro.distributed.sharding import use_mesh
    from repro.launch import shardings as shlib
    from repro.models.registry import get_model
    from repro.optim.adamw import AdamWConfig, init_adamw
    from repro.train.step import (make_prefill_step, make_serve_step,
                                  make_train_step)

    api = get_model(cfg)
    t0 = time.time()
    with use_mesh(mesh, act_rules=opts.get("act_rules")):
        key = jax.random.PRNGKey(0)
        boxed_struct = jax.eval_shape(api.init, key)
        params_struct, params_sh = shlib.params_shardings(boxed_struct, mesh)
        specs = api.input_specs(shape)
        batch_sh = shlib.batch_shardings(specs, mesh)

        if shape.kind == "train":
            opt_cfg = AdamWConfig()
            opt_struct = jax.eval_shape(
                lambda p: init_adamw(p, opt_cfg), params_struct)
            opt_sh = shlib.opt_shardings(opt_struct, params_sh, mesh)
            step = make_train_step(api, opt_cfg,
                                   microbatches=opts.get("microbatches", 1))
            jitted = jax.jit(step,
                             in_shardings=(params_sh, opt_sh, batch_sh),
                             out_shardings=(params_sh, opt_sh, None),
                             donate_argnums=(0, 1))
            lowered = jitted.lower(params_struct, opt_struct, specs)
        elif shape.kind == "prefill":
            step = make_prefill_step(api)
            jitted = jax.jit(step, in_shardings=(params_sh, batch_sh),
                             out_shardings=None)
            lowered = jitted.lower(params_struct, specs)
        else:  # decode
            states_struct = jax.eval_shape(
                lambda: api.init_states(shape.global_batch, shape.seq_len))
            states_sh = shlib.state_shardings(states_struct, mesh)
            step = make_serve_step(api)
            tokens_spec = specs.pop("tokens")
            tokens_sh = shlib.batch_shardings({"tokens": tokens_spec},
                                              mesh)["tokens"]
            extra_sh = shlib.batch_shardings(specs, mesh) if specs else None
            jitted = jax.jit(
                step,
                in_shardings=(params_sh, tokens_sh, states_sh, extra_sh),
                out_shardings=(None, None, states_sh),
                donate_argnums=(2,))
            lowered = jitted.lower(params_struct, tokens_spec, states_struct,
                                   specs if specs else None)

        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
    return compiled, (t_lower, t_compile)


def _cost_vector(compiled) -> dict:
    cost = compiled.cost_analysis()
    out = {"flops": 0.0, "bytes accessed": 0.0, "transcendentals": 0.0}
    if isinstance(cost, dict):
        for k in out:
            out[k] = float(cost.get(k, 0.0) or 0.0)
    coll = parse_collective_bytes(compiled.as_text())
    out["collective_bytes"] = float(coll["total_bytes_per_chip"])
    out["_collectives"] = coll
    return out


def _mech_name(cfg):
    from repro.core.mechanism import resolve_mechanism_name

    return resolve_mechanism_name(cfg.attention)


def build_cell(arch: str, shape_name: str, multi_pod: bool,
               attention_kind=None, *, opts=None, layer_extrapolate=True):
    """Lower+compile one cell. Returns the result record dict.

    Cost correction: XLA HLO cost analysis counts a While (lax.scan) body
    ONCE, not ×trip_count — verified empirically (ratio exactly equals the
    trip count).  We therefore lower unrolled depth-1 and depth-2 variants
    of the model at the same shape/mesh and extrapolate:
        corrected(L) = cost(d1) + (L − 1)·(cost(d2) − cost(d1))
    which is exact because every per-layer quantity (layer FLOPs, layer
    optimizer update, layer gradient collectives) is linear in depth.
    """
    import dataclasses

    from repro.configs import SHAPES_BY_NAME, get_config
    from repro.launch.mesh import make_production_mesh

    opts = opts or {}
    cfg = get_config(arch if attention_kind is None
                     else f"{arch}@{attention_kind}")
    if opts.get("remat"):
        cfg = dataclasses.replace(cfg, remat=opts["remat"])
    shape = SHAPES_BY_NAME[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)

    compiled, (t_lower, t_compile) = _lower_one(cfg, shape, mesh, opts)
    mem = compiled.memory_analysis()
    raw = _cost_vector(compiled)

    corrected = None
    if layer_extrapolate:
        try:
            # aux lowerings use microbatches=1: the gradient-accumulation
            # scan is ALSO a While whose body cost analysis counts once,
            # and total step FLOPs/bytes are mb-invariant (same tokens)
            aux_opts = {k: v for k, v in opts.items()
                        if k != "microbatches"}
            c1, _ = _lower_one(cfg.with_layers(1, unroll=True), shape, mesh,
                               aux_opts)
            c2, _ = _lower_one(cfg.with_layers(2, unroll=True), shape, mesh,
                               aux_opts)
            v1, v2 = _cost_vector(c1), _cost_vector(c2)
            L = cfg.num_layers
            corrected = {
                k: v1[k] + (L - 1) * (v2[k] - v1[k])
                for k in ("flops", "bytes accessed", "transcendentals",
                          "collective_bytes")
            }
        except Exception as e:  # noqa: BLE001
            corrected = {"error": f"{type(e).__name__}: {e}"}

    def _mem_field(name):
        v = getattr(mem, name, None)
        return int(v) if v is not None else None

    record = {
        "arch": arch,
        "attention_kind": attention_kind or _mech_name(cfg),
        "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "chips": 512 if multi_pod else 256,
        "kind": shape.kind,
        "ok": True,
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "memory": {
            "argument_bytes": _mem_field("argument_size_in_bytes"),
            "output_bytes": _mem_field("output_size_in_bytes"),
            "temp_bytes": _mem_field("temp_size_in_bytes"),
            "generated_code_bytes": _mem_field(
                "generated_code_size_in_bytes"),
        },
        "cost_raw": {k: raw[k] for k in
                     ("flops", "bytes accessed", "transcendentals",
                      "collective_bytes")},
        "cost_per_chip": corrected,
        "collectives": raw["_collectives"],
        "opts": opts or {},
    }
    return record


def run_cell(arch, shape_name, multi_pod, attention_kind=None, opts=None,
             out_dir="experiments/dryrun"):
    os.makedirs(out_dir, exist_ok=True)
    tag = (f"{arch}_{shape_name}_{'2x16x16' if multi_pod else '16x16'}"
           + (f"_{attention_kind}" if attention_kind else "")
           + (f"_{opts['tag']}" if opts and opts.get("tag") else ""))
    try:
        rec = build_cell(arch, shape_name, multi_pod, attention_kind,
                         opts=opts)
    except Exception as e:  # noqa: BLE001 — record the failure, keep going
        rec = {"arch": arch, "shape": shape_name,
               "mesh": "2x16x16" if multi_pod else "16x16",
               "attention_kind": attention_kind, "ok": False,
               "error": f"{type(e).__name__}: {e}",
               "traceback": traceback.format_exc()[-4000:]}
    path = os.path.join(out_dir, tag + ".json")
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    status = "OK" if rec.get("ok") else "FAIL"
    mem = rec.get("memory", {}).get("temp_bytes")
    print(f"[{status}] {tag}  temp={mem/1e9:.2f}GB" if mem else
          f"[{status}] {tag}", flush=True)
    if not rec.get("ok"):
        print("   ", rec.get("error"), flush=True)
    return rec


def cell_matrix():
    """The assigned 40 cells (+ noted skips) per DESIGN.md §5."""
    from repro.configs import ARCH_IDS, LONG_CONTEXT_ARCHS
    from repro.configs.base import SHAPES

    cells = []
    for arch in ARCH_IDS:
        for shape in SHAPES:
            skip = (shape.name == "long_500k"
                    and arch not in LONG_CONTEXT_ARCHS)
            cells.append((arch, shape.name, skip))
    return cells


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--attention", default=None,
                    help="override attention kind (inhibitor|dotprod|...)")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true",
                    help="run the full single-pod baseline matrix")
    ap.add_argument("--multi-pod-all", action="store_true",
                    help="also run every cell on the 2x16x16 mesh")
    ap.add_argument("--list", action="store_true")
    ap.add_argument("--out-dir", default="experiments/dryrun")
    ap.add_argument("--remat", default=None)
    ap.add_argument("--tag", default=None)
    ap.add_argument("--microbatches", type=int, default=4,
                    help="gradient-accumulation microbatches (train shapes)")
    args = ap.parse_args(argv)

    if args.list:
        for arch, shape, skip in cell_matrix():
            print(f"{arch:28s} {shape:12s}"
                  + ("  [skip: full-attention @ 500k]" if skip else ""))
        return 0

    opts = {"microbatches": args.microbatches}
    if args.remat:
        opts["remat"] = args.remat
    if args.tag:
        opts["tag"] = args.tag

    if args.all or args.multi_pod_all:
        import subprocess
        failures = 0
        for arch, shape, skip in cell_matrix():
            if skip:
                print(f"[SKIP] {arch}_{shape} (full-attention @ 500k — "
                      "DESIGN.md §5)", flush=True)
                continue
            meshes = [False] if args.all and not args.multi_pod_all else []
            if args.multi_pod_all:
                meshes = [False, True] if args.all else [True]
            for mp in meshes:
                # one subprocess per cell: isolates compiler memory and any
                # single-cell crash from the rest of the matrix
                cmd = [sys.executable, "-m", "repro.launch.dryrun",
                       "--arch", arch, "--shape", shape,
                       "--out-dir", args.out_dir,
                       "--microbatches", str(args.microbatches)]
                if mp:
                    cmd.append("--multi-pod")
                if args.attention:
                    cmd += ["--attention", args.attention]
                if args.remat:
                    cmd += ["--remat", args.remat]
                if args.tag:
                    cmd += ["--tag", args.tag]
                r = subprocess.run(cmd, timeout=3600)
                failures += 0 if r.returncode == 0 else 1
        print(f"done; {failures} failures", flush=True)
        return 1 if failures else 0

    assert args.arch and args.shape, "--arch and --shape (or --all)"
    rec = run_cell(args.arch, args.shape, args.multi_pod, args.attention,
                   opts or None, args.out_dir)
    return 0 if rec.get("ok") else 1


if __name__ == "__main__":
    sys.exit(main())
