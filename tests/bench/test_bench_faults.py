"""A run whose timed path is broken underneath comes out not correct: the
harness is driven as in a benchmark run (its look for a chip skipped),
over an engine with one fault planted."""

import jax.numpy as jnp
import pytest
from bench_helpers import serve, tiny_cell

from repro.serve.engine import Engine


def _token_altered(monkeypatch):
    """Every produced token is the argmax's neighbour."""
    def select(self, logits, key):
        return ((jnp.argmax(logits, axis=-1) + 1)
                % logits.shape[-1]).astype(jnp.int32)
    monkeypatch.setattr(Engine, "_select", select)


def _state_unchanged(monkeypatch):
    """The decode step returns the KV state it was given."""
    orig = Engine._decode_step

    def step(self, params, tokens, states, key):
        nxt, _ = orig(self, params, tokens, states, key)
        return nxt, states
    monkeypatch.setattr(Engine, "_decode_step", step)


def _half_batch(monkeypatch):
    """The decode step leaves out the second half of the batch."""
    orig = Engine._decode_step

    def step(self, params, tokens, states, key):
        nxt, new = orig(self, params, tokens, states, key)
        half = nxt.shape[0] // 2
        return nxt.at[half:].set(0), new
    monkeypatch.setattr(Engine, "_decode_step", step)


@pytest.mark.parametrize("fault", [_token_altered, _state_unchanged,
                                   _half_batch],
                         ids=["token_altered", "state_unchanged",
                              "half_batch"])
@pytest.mark.parametrize("mechanism", ["inhibitor", "dotprod"])
def test_planted_fault_makes_the_run_not_correct(monkeypatch, fault,
                                                 mechanism):
    fault(monkeypatch)
    res = serve(tiny_cell(mechanism, "offline"))
    assert res["correct"] is False
    chk = res["checks"]["max_logit_gap"]
    assert chk["value"] > chk["limit"]
