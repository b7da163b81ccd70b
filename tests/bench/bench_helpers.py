"""Helpers of the benchmark's own tests: the checkout root on the import
path (the benchmark imports as ``bench``) and a tiny cell that the
harness can serve on the CPU."""

import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def tiny_config(mechanism: str) -> dict:
    """The cell's configuration file cut to the program's ``reduced()``
    model (2 layers, d_model 64, 4/2 heads of 16, vocab 256, float32),
    with a wider initializer so that its logits are not one token's."""
    base = json.loads((ROOT / "bench" / "configs" /
                       f"smollm-135m.{mechanism}.json").read_text())
    c = dict(base)
    c.update({"hidden_size": 64, "intermediate_size": 128,
              "num_hidden_layers": 2, "num_attention_heads": 4,
              "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 256,
              "initializer_range": 0.25, "program_reduced": True,
              "dtypes": {"params": "float32", "compute": "float32",
                         "kv_cache": "float32"},
              "engine": {"max_batch": 4, "max_len": 128, "tick_budget": 64,
                         "prefix_cache": True, "warmup": "none"},
              "check": {"max_logit_gap": 1e-3}})
    c["attention"] = dict(c["attention"], score_scale=4.0)
    return c


#: An open-loop multi-turn chat mix, as a chat cell's data file would
#: hold it (its ``rate_sessions_s`` set there from a knee sweep on the
#: chip): Poisson session starts, one shared 512-token system prompt,
#: 1-4 turns, each prompt the system prompt, the session's earlier
#: messages and served answers, then the new message.
CHAT_MIX = {
    "loop": "open", "rate_sessions_s": 1.0, "ramp_s": 15.0, "block": 64,
    "system_prompt": 512,
    "turns": {"dist": "uniform_int", "min": 1, "max": 4},
    "prompt": {"dist": "lognormal", "median": 96, "sigma": 0.7, "min": 16,
               "max": 384},
    "output": {"dist": "lognormal", "median": 128, "sigma": 0.6, "min": 16,
               "max": 384},
    "think_s": {"dist": "exponential", "mean": 2.0},
}


def mix_file(name: str) -> dict:
    """A mix as its data file holds it; ``chat`` is :data:`CHAT_MIX`."""
    if name == "chat":
        return dict(CHAT_MIX)
    return json.loads((ROOT / "bench" / "traffic" / f"{name}.json").read_text())


def tiny_mix(name: str) -> dict:
    mix = mix_file(name)
    if name == "offline":
        mix.update(concurrency=4,
                   prompt=dict(mix["prompt"], median=16, min=4, max=40),
                   output=dict(mix["output"], median=12, min=4, max=24))
    else:
        mix.update(rate_sessions_s=4.0, ramp_s=2.0, system_prompt=32,
                   prompt=dict(mix["prompt"], median=8, min=4, max=16),
                   output=dict(mix["output"], median=8, min=4, max=16),
                   think_s=dict(mix["think_s"], mean=0.1))
    return mix


def tiny_cell(mechanism: str, mix: str):
    """A ``harness.Cell`` with the real cell's metrics over the tiny
    configuration and mix."""
    from bench import harness

    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    name = f"smollm-135m.{mechanism}.{mix}"
    return harness.Cell(
        name=name, config_name=f"smollm-135m.{mechanism}",
        config=tiny_config(mechanism), traffic_name=mix,
        mix=tiny_mix(mix), chips=1,
        end_to_end=harness._for_cell(spec["end_to_end"], name),
        per_layer=harness._for_cell(spec["per_layer"], name))


CPU_PEAKS = {"bf16_flop_s": 197e12, "hbm_bytes_s": 819e9}


def serve(cell, seed=12345, seconds=1.5, trace=False):
    """One harness run of ``cell`` on the CPU (the look for a chip
    skipped): the result object the benchmark's last line prints."""
    import time

    from bench import harness

    return harness.run_cell(cell, seed, seconds, trace,
                            t_start=time.perf_counter(), require_tpu=False,
                            peaks=CPU_PEAKS, log=lambda *a: None)
