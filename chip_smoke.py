"""Chip smoke test: the serving path at full width on one TPU.

    python chip_smoke.py              # one chip: serve both attention arms
    python chip_smoke.py --chips 4    # four chips: sharded training only

One process; nothing here starts another.  With no arguments it builds
``smollm-135m`` at its published widths (30 layers, d_model 576, 9/3
heads, head_dim 64, vocab 49,152; random weights from ``--seed``) behind
``Engine`` through ``launch.serve.build_engine``, once per attention
mechanism (``dotprod`` and ``inhibitor``), with a real paged KV pool and
every serving shape compiled up front (``warmup="serve"``).  It serves
two waves of greedy requests (prompts of 100-1,500 tokens, half of them
sharing a 256-token prefix, under a per-tick token budget) and checks:

  * the engine is paged and plans ``paged_pallas`` decode, the paged
    kernel family runs natively (not in interpret mode), and every
    kernel-registry decision is native;
  * measured compiles stay within the statically proven retrace budget;
  * the second wave mounts cached prefix pages;
  * greedy parity: a teacher-forced whole-sequence forward of the same
    model (no KV cache, no pages, flash kernels) over prompt + generated
    tokens ranks each generated token within ``PARITY_TOL`` logits of its
    top token at that position.  bf16 logits of a random-weight model
    tie often, so an exact argmax match is counted and printed but a
    near-tie inside the tolerance passes.

``--chips 4`` runs the data-parallel training path of ``launch.train``
(``--data-parallel 4 --model-parallel 1``: 9 heads do not split over a
model axis of 2 or 4) for a few full-width steps and compares loss and
parameters with the same steps on one device, and checks the parameters
are placed across all four devices.

Set-up and compile seconds are printed as diagnostics, not speed claims.
Any failed check exits nonzero.  The last line of standard output, on
success only, is one JSON object naming the device.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

ARCH = "smollm-135m"
ARMS = ("dotprod", "inhibitor")
MAX_BATCH, MAX_LEN, PAGE = 16, 2048, 16
NEW_TOKENS = 8
TICK_BUDGET = 256
SHARED_PREFIX = 256
# (prompt length, shares the prefix) per request, in two waves: the
# second wave's shared requests find the first wave's prefix cached
WAVES = (((1400, True), (600, True), (100, False), (1500, False)),
         ((900, True), (300, True), (1200, False), (450, False)))
REF_LEN = 1536           # reference forward width (>= prompt + new tokens)
PARITY_TOL = 0.125       # logits: 8 bf16 ulps at |x| in [2, 4)
TRAIN_STEPS = 3
LOSS_RTOL = 1e-3
PARAM_TOL = 2e-3


class CheckFailed(Exception):
    pass


def check(ok: bool, what: str) -> None:
    print(f"  [{'ok' if ok else 'FAIL'}] {what}", flush=True)
    if not ok:
        raise CheckFailed(what)


def make_prompts(vocab: int, seed: int):
    rng = np.random.default_rng(seed)
    shared = rng.integers(0, vocab, SHARED_PREFIX).astype(np.int32)
    waves = []
    for wave in WAVES:
        prompts = []
        for n, share in wave:
            if share:
                tail = rng.integers(0, vocab, n - SHARED_PREFIX)
                prompts.append(np.concatenate([shared, tail]).astype(np.int32))
            else:
                prompts.append(rng.integers(0, vocab, n).astype(np.int32))
        waves.append(prompts)
    return waves


def reference_margins(forward, params, prompt, output):
    """Teacher-forced reference: ``forward``'s logits at the positions
    that produced ``output`` -> (margin of each engine token below the
    top logit, whether each is the exact argmax).  Right padding is
    causally invisible to the positions read."""
    import jax.numpy as jnp

    seq = np.concatenate([prompt, np.asarray(output[:-1], np.int32)])
    toks = np.zeros((1, REF_LEN), np.int32)
    toks[0, :len(seq)] = seq
    logits = forward(params, jnp.asarray(toks))
    start = len(prompt) - 1
    lg = np.asarray(logits[0, start:start + len(output)], np.float32)
    got = lg[np.arange(len(output)), np.asarray(output)]
    return lg.max(-1) - got, lg.argmax(-1) == np.asarray(output)


def serve_arm(mech: str, seed: int) -> dict:
    import jax

    from repro.kernels.ops import registry as kernel_registry
    from repro.launch.serve import build_engine
    from repro.serve.engine import EngineConfig, Request

    name = ARCH if mech == "dotprod" else f"{ARCH}@{mech}"
    print(f"== arm {name}", flush=True)
    kernel_registry.decisions.clear()
    t0 = time.perf_counter()
    eng = build_engine(name, EngineConfig(
        max_batch=MAX_BATCH, max_len=MAX_LEN, page_size=PAGE,
        tick_budget=TICK_BUDGET, warmup="serve"), seed=seed)
    setup_s = time.perf_counter() - t0
    cfg = eng.api.cfg
    a = cfg.attention
    print(f"  model: {cfg.name} layers={cfg.num_layers} d_model="
          f"{cfg.d_model} heads={a.num_heads}/{a.num_kv_heads} head_dim="
          f"{a.head_dim} vocab={cfg.vocab_size} mechanism={a.mechanism}")
    print(f"  pool: {eng.alloc.num_pages} pages x {PAGE} tokens, "
          f"max_batch={MAX_BATCH} max_len={MAX_LEN}")
    print(f"  set-up incl. warmup compiles: {setup_s:.1f}s "
          f"(decode compiles {eng.decode_compiles}, prefill compiles "
          f"{eng.prefill_compiles})", flush=True)
    check(eng.paged, "engine is paged")
    check(eng.decode_plan is not None
          and eng.decode_plan.backend == "paged_pallas",
          f"decode plan backend = {eng.decode_plan.backend}")
    check(kernel_registry.interpret_for("paged") is False,
          "paged kernels lower natively (no interpret mode)")

    served = []
    t0 = time.perf_counter()
    rid = 0
    for w, prompts in enumerate(make_prompts(cfg.vocab_size, seed)):
        reqs = []
        for p in prompts:
            reqs.append(Request(rid, p, max_new_tokens=NEW_TOKENS))
            eng.submit(reqs[-1])
            rid += 1
        done = eng.run_to_completion()
        check(len(done) == len(reqs)
              and all(len(r.output) == NEW_TOKENS for r in reqs),
              f"wave {w}: {len(done)}/{len(reqs)} requests served, "
              f"{NEW_TOKENS} tokens each")
        served += reqs
    serve_s = time.perf_counter() - t0
    stats = eng.stats()
    budget = eng.retrace_budget()
    print(f"  served {len(served)} requests in {serve_s:.1f}s "
          f"(prefill_tokens={stats['prefill_tokens']} "
          f"prefix_hit_tokens={stats['prefix_hit_tokens']} "
          f"decode_ticks={stats['decode_ticks']} "
          f"high_water_pages={stats['high_water_pages']})", flush=True)
    check(stats["prefix_hit_tokens"] > 0, "second wave mounted the "
          "cached shared prefix")
    check(eng.prefill_compiles <= budget["prefill_proven"]
          and eng.decode_compiles <= budget["decode_proven"],
          f"compiles within proven budget: prefill {eng.prefill_compiles}"
          f"/{budget['prefill_proven']}, decode {eng.decode_compiles}/"
          f"{budget['decode_proven']}")
    decisions = kernel_registry.decisions
    check(bool(decisions) and all(d["native"] for d in decisions.values()),
          f"{len(decisions)} kernel-registry decisions, all native: "
          + ", ".join(sorted({f'{k[0]}:{d["source"]}'
                              for k, d in decisions.items()})))

    forward = jax.jit(lambda p, t: eng.api.forward(p, {"tokens": t})[0])
    worst, exact, total = 0.0, 0, 0
    for r in served:
        margin, hit = reference_margins(forward, eng.params, r.prompt,
                                        r.output)
        worst = max(worst, float(margin.max()))
        exact += int(hit.sum())
        total += len(hit)
        print(f"  req {r.request_id} len={len(r.prompt)} tokens="
              f"{r.output} exact={int(hit.sum())}/{len(hit)} "
              f"max_margin={float(margin.max()):.4f}", flush=True)
    check(worst <= PARITY_TOL,
          f"greedy parity with the teacher-forced reference: {exact}/"
          f"{total} exact argmax, worst margin {worst:.4f} <= "
          f"{PARITY_TOL}")
    return {"setup_s": setup_s, "serve_s": serve_s, "served": len(served)}


def train_sharded(seed: int) -> None:
    import jax

    from repro.launch import train as train_cli

    def run(dp: int):
        args = train_cli.parse_args([
            "--arch", ARCH, "--full", "--steps", str(TRAIN_STEPS),
            "--batch", "8", "--seq", "128", "--seed", str(seed),
            "--data-parallel", str(dp), "--model-parallel", "1",
            "--max-restarts", "0"])
        t0 = time.perf_counter()
        res = train_cli.run(args)
        print(f"  {dp}-device run: {time.perf_counter() - t0:.1f}s incl. "
              f"compile, losses {[h['loss'] for h in res['history']]}",
              flush=True)
        return res

    print(f"== sharded training: {ARCH} full width, data=4 model=1, "
          f"{TRAIN_STEPS} steps", flush=True)
    one = run(1)
    four = run(4)
    leaves = jax.tree.leaves(four["params"])
    devices = {d for leaf in leaves for d in leaf.sharding.device_set}
    split = [leaf for leaf in leaves
             if not leaf.sharding.is_fully_replicated]
    check(len(devices) == 4, f"sharded params span {len(devices)} devices")
    check(len(split) > 0, f"{len(split)}/{len(leaves)} param leaves are "
          f"split across devices (not replicated)")
    for h1, h4 in zip(one["history"], four["history"]):
        check(abs(h1["loss"] - h4["loss"]) <= LOSS_RTOL * max(1.0,
                                                             abs(h1["loss"])),
              f"step {h1['step']}: loss 1-device {h1['loss']:.6f} vs "
              f"4-device {h4['loss']:.6f}")
    worst = 0.0
    for a, b in zip(jax.tree.leaves(one["params"]), leaves):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        worst = max(worst, float(np.max(np.abs(a - b)
                                        - PARAM_TOL * np.abs(a))))
    check(worst <= PARAM_TOL, f"params after {TRAIN_STEPS} steps agree "
          f"(max |diff| - rtol*|ref| = {worst:.2e} <= {PARAM_TOL})")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    src = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"chip_smoke: no repro package under {src}", file=sys.stderr)
        return 2
    sys.path.insert(0, src)
    from repro.launch.compile_cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}", flush=True)
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        print(f"chip_smoke: no JAX backend: {e}", file=sys.stderr)
        return 1
    dev = devices[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}", flush=True)
    if dev.platform != "tpu":
        print("chip_smoke: no TPU; this check runs only on the chip",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devices)} "
              f"device(s)", file=sys.stderr)
        return 1

    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            train_sharded(args.seed)
        else:
            for mech in ARMS:
                serve_arm(mech, args.seed)
    except CheckFailed as e:
        print(f"chip_smoke: check failed: {e}", file=sys.stderr)
        return 1
    print(f"total {time.perf_counter() - t0:.1f}s", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
