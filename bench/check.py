"""The comparison that decides ``correct``: served tokens against the plain
reference.

Once the window has closed and the engine is freed, a sample of the
requests the engine finished, drawn from the seed and always holding the
longest, is run through the reference once each, teacher-forced on the
prompt and the served tokens.  At every position that produced a served
token, the gap is the reference's best logit minus the reference's logit
of the served token (0 when the served token is the reference's argmax).
The number compared is the widest gap over the sample.

The control puts the reference computed in float8 (``precision="fp8"``)
in the program's place: at the same positions it reads the gap, under the
float32 reference, of the token the float8 reference ranks first.
"""

from __future__ import annotations

import functools
import importlib.util
import json
import pathlib
from typing import List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

SAMPLE = 6          # requests compared per run, the longest among them

REFERENCES = pathlib.Path(__file__).resolve().parent / "references"


def load_reference(name: str):
    path = REFERENCES / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no reference {path}")
    spec = importlib.util.spec_from_file_location(f"bench_ref_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def sample(finished: Sequence, seed: int, k: int = SAMPLE) -> List:
    """Up to ``k`` finished records (with ``prompt`` and ``output``), the
    one with the longest prompt + output first, the rest drawn from the
    seed."""
    if not finished:
        return []
    order = sorted(finished, key=lambda r: (-(len(r.prompt) + len(r.output)),
                                            r.rid))
    longest, rest = order[0], sorted(order[1:], key=lambda r: r.rid)
    rng = np.random.default_rng(np.random.SeedSequence([seed, 7]))
    pick = rng.permutation(len(rest))[:k - 1]
    return [longest] + [rest[i] for i in sorted(pick)]


@functools.lru_cache(maxsize=None)
def _gaps_fn(ref_name: str, key: str, control: bool):
    """One compiled comparison per (configuration, control)."""
    ref = load_reference(ref_name)
    f32 = ref.jitted(key, "f32")
    low = ref.jitted(key, "fp8") if control else None

    def gaps(w, tokens, targets, valid):
        lg = f32(w, tokens)                                   # (T, V)
        best = jnp.max(lg, axis=-1)
        got = jnp.take_along_axis(lg, targets[:, None], axis=-1)[:, 0]
        served = jnp.max(jnp.where(valid, best - got, 0.0))
        if low is None:
            return served, served
        pick = jnp.argmax(low(w, tokens), axis=-1)
        alt = jnp.take_along_axis(lg, pick[:, None], axis=-1)[:, 0]
        return served, jnp.max(jnp.where(valid, best - alt, 0.0))

    return jax.jit(gaps)


def compare(c: dict, weights, seqs: Sequence, *, control: bool = False):
    """Widest gap over ``seqs`` (each with ``prompt`` and ``output``), and
    the control's widest gap at the same positions when ``control``.
    Returns (served_gap, control_gap or None, tokens compared)."""
    ref = load_reference(c["reference"])
    T = -(-c["engine"]["max_len"] // ref.Q_BLOCK) * ref.Q_BLOCK
    fn = _gaps_fn(c["reference"], json.dumps(c, sort_keys=True), control)
    worst, worst_ctrl, n = 0.0, 0.0, 0
    for r in seqs:
        prompt = np.asarray(r.prompt, np.int32)
        out = np.asarray(r.output, np.int32)
        seq = np.concatenate([prompt, out[:-1]])
        if len(seq) > T:
            raise ValueError(f"sequence of {len(seq)} tokens exceeds {T}")
        tokens = np.zeros(T, np.int32)
        tokens[:len(seq)] = seq
        targets = np.zeros(T, np.int32)
        valid = np.zeros(T, bool)
        at = np.arange(len(prompt) - 1, len(prompt) - 1 + len(out))
        targets[at] = out
        valid[at] = True
        g, gc = fn(weights, jnp.asarray(tokens), jnp.asarray(targets),
                   jnp.asarray(valid))
        worst = max(worst, float(g))
        worst_ctrl = max(worst_ctrl, float(gc))
        n += len(out)
    return worst, (worst_ctrl if control else None), n
