"""The comparison that decides ``correct``, at a size a test run holds:
sound runs pass, and the float8 control, put in the program's place at
the same positions, fails the limit."""

import pytest
from bench_helpers import serve, tiny_cell

from bench import check, harness, traffic, weights


@pytest.mark.parametrize("mechanism", ["inhibitor", "dotprod"])
@pytest.mark.parametrize("mix", ["offline", "chat"])
def test_sound_run_is_correct(mechanism, mix):
    res = serve(tiny_cell(mechanism, mix), seconds=3.0)
    assert res["correct"] is True
    assert res["checks"]["max_logit_gap"]["value"] <= (
        res["checks"]["max_logit_gap"]["limit"])
    assert res["checks"]["tokens_compared"]["value"] > 0
    assert set(res["metrics"]) == {m["name"] for m in
                                   tiny_cell(mechanism, mix).end_to_end}
    assert list(res)[-1] == "checks"


#: requests the control's runs drive to their end: more than a sample, so
#: the sample is drawn, and a fixed count, so a loaded CPU serves the same
#: requests as an idle one
CONTROL_FINISHED = 2 * check.SAMPLE


@pytest.mark.parametrize("mechanism", ["inhibitor", "dotprod"])
def test_float8_control_fails_the_limit(mechanism):
    cell = tiny_cell(mechanism, "offline")
    c = cell.config
    for seed in (11, 12, 13):
        eng = harness.build_engine(c, seed)
        gen = traffic.Traffic(cell.mix, vocab=c["vocab_size"],
                              max_len=c["engine"]["max_len"], seed=seed)
        win = harness.drive(eng, gen, 0.0, min_finished=CONTROL_FINISHED)
        assert len(win.finished) >= CONTROL_FINISHED
        seqs = check.sample(win.finished, seed)
        gap, ctrl, n = check.compare(c, weights.make(c, seed), seqs,
                                     control=True)
        limit = c["check"]["max_logit_gap"]
        assert n > 0
        assert gap <= limit < ctrl


def test_sample_holds_the_longest_and_is_seeded():
    done = [harness._Done(i, [0] * (i % 7 + 1), [1] * (i % 5 + 1))
            for i in range(20)]
    a, b = check.sample(done, 3), check.sample(done, 3)
    assert [r.rid for r in a] == [r.rid for r in b]
    assert len(a) == check.SAMPLE
    longest = max(done, key=lambda r: len(r.prompt) + len(r.output))
    assert a[0].rid == longest.rid
    assert [r.rid for r in check.sample(done, 4)] != [r.rid for r in a]
