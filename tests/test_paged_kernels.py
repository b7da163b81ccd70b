"""Paged decode kernels (kernels/paged.py) + the kernel registry
(kernels/ops.py): interpret-mode parity sweeps against the gather
references, the fused paged backend, trash-page isolation, and the
registry's choice/override plumbing (DESIGN.md §10).

The sweep covers the decode shapes the serve engine actually produces:
GQA groups, sliding windows, ragged per-row cursors, cursors that
straddle a page boundary / land exactly on one / sit at a single token,
and the ``normalize`` flag — plus the cases the page walk creates:
blocks that the live pages do not fill, a row filling the whole table,
a one-column table, rows of one token, GQA groups of 1 and 7, head_dim
128, bf16 pools, and a window whose oldest position is mid-page.
"""

import dataclasses
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.mechanism import (ExecutionPlan, MechanismParams,
                                  PagedLayout, Structural, execute_plan,
                                  get_mechanism)
from repro.kernels import ops as kops, ref as kref
from repro.kernels.paged import (paged_flash_attention_fwd,
                                 paged_flash_inhibitor_fwd)

TOL = dict(rtol=1e-4, atol=1e-5)
EPS32 = float(np.finfo(np.float32).eps)


def _pool(rng, *, batch, pages_per_slot, page_size, kv_heads, d,
          lengths, dtype=np.float32, v_scale=1.0):
    """A ragged head-major paged pool (num_pages, kv_heads, page_size, d):
    per-row non-contiguous physical pages, trash page 0 for every
    unmapped table entry (the engine's layout)."""
    num_pages = batch * pages_per_slot + 1
    # drawn token-major, stored head-major: the same KV content per
    # (page, offset, head) as a token-major pool drawn from this rng
    kp = rng.normal(size=(num_pages, page_size, kv_heads, d))
    vp = rng.normal(size=(num_pages, page_size, kv_heads, d)) * v_scale
    kp, vp = kp.transpose(0, 2, 1, 3), vp.transpose(0, 2, 1, 3)
    perm = rng.permutation(np.arange(1, num_pages))
    tables = np.zeros((batch, pages_per_slot), np.int32)
    nxt = 0
    for b, ln in enumerate(lengths):
        used = -(-int(ln) // page_size)
        tables[b, :used] = perm[nxt:nxt + used]
        nxt += used
    return (jnp.asarray(kp.astype(np.float32), dtype),
            jnp.asarray(vp.astype(np.float32), dtype), jnp.asarray(tables),
            jnp.asarray(np.asarray(lengths, np.int32)))


# page_size 8: 13 straddles a boundary, 8 lands exactly on one, 1 is a
# single token, 24 fills three pages
RAGGED_LENGTHS = [13, 8, 1, 24]


@dataclasses.dataclass(frozen=True)
class Case:
    """One decode shape: a pool of ``len(lengths)`` rows."""
    lengths: Tuple[int, ...] = tuple(RAGGED_LENGTHS)
    pages_per_slot: int = 4
    page_size: int = 8
    heads: int = 4
    kv_heads: int = 2                  # GQA group of 2
    d: int = 16
    dtype: type = np.float32           # the pools'
    q_dtype: type = np.float32
    window: Optional[int] = None
    pages_per_step: Optional[int] = None


BASE = Case()
#: shapes the page walk creates, beside the base sweep
WALK_CASES = {
    # 8, 5, 3 and 2 live pages in blocks of 3: every block count and
    # remainder; the 61-token row fills its table
    "block-remainder": Case(lengths=(61, 40, 17, 9), pages_per_slot=8,
                            pages_per_step=3),
    "full-table": Case(lengths=(32, 5, 32)),
    "table-width-1": Case(lengths=(5, 8, 1), pages_per_slot=1),
    "length-1": Case(lengths=(1, 1, 1)),
    "group-1": Case(heads=2, kv_heads=2),
    "group-7": Case(heads=14, kv_heads=2),
    "d-128": Case(d=128),
    "bf16-pools": Case(dtype=jnp.bfloat16),
    # the serving dtypes: bf16 queries against bf16 pages
    "bf16-queries": Case(dtype=jnp.bfloat16, q_dtype=jnp.bfloat16),
    # oldest attended position 29 - 11 = 18: page 2, offset 2; the walk
    # starts at page 2
    "window-mid-page": Case(lengths=(29, 24, 13, 3), window=11),
}


def _case_inputs(rng, case, *, v_scale=1.0):
    kp, vp, tables, lengths = _pool(
        rng, batch=len(case.lengths), pages_per_slot=case.pages_per_slot,
        page_size=case.page_size, kv_heads=case.kv_heads, d=case.d,
        lengths=case.lengths, dtype=case.dtype, v_scale=v_scale)
    q = jnp.asarray(rng.normal(size=(len(case.lengths), 1, case.heads,
                                     case.d)).astype(np.float32),
                    case.q_dtype)
    return q, kp, vp, tables, lengths


def _inhibitor_f64(q, kp, vp, tables, lengths, *, signed, normalize, window,
                   score_shift=0.5):
    """Float64 numpy oracle of the paged inhibitor (eq. 5, 7, 9), one row
    and one head at a time from the pages themselves.  Returns the
    output and, per row, the keys it summed and the largest |term|."""
    q, kp, vp = (np.asarray(jnp.asarray(a, jnp.float32), np.float64)
                 for a in (q, kp, vp))
    tables, lengths = np.asarray(tables), np.asarray(lengths)
    batch, _, heads, d = q.shape
    kv_heads, ps = kp.shape[1], kp.shape[2]
    group = heads // kv_heads
    out = np.zeros(q.shape)
    n_keys = np.zeros(batch)
    scale_of_terms = np.zeros(batch)
    for r in range(batch):
        pos = np.arange(min(lengths[r], tables.shape[1] * ps))
        if window is not None:
            pos = pos[pos > lengths[r] - 1 - window]
        pages, offs = tables[r, pos // ps], pos % ps
        n_keys[r] = len(pos)
        for h in range(heads):
            k = kp[pages, h // group, offs]                    # (n, d)
            v = vp[pages, h // group, offs]
            z = np.abs(q[r, 0, h] - k).sum(-1) / math.sqrt(d)
            z = np.maximum(z - score_shift, 0.0)[:, None]
            if signed:
                terms = (np.maximum(np.maximum(v, 0) - z, 0)
                         - np.maximum(np.maximum(-v, 0) - z, 0))
            else:
                terms = np.maximum(v - z, 0)
            out[r, 0, h] = terms.sum(0) / (max(len(pos), 1)
                                           if normalize else 1)
            scale_of_terms[r] = max(scale_of_terms[r],
                                    np.abs(v).max(initial=0) + z.max(
                                        initial=0))
    return out, n_keys, scale_of_terms


def _f32_sum_tolerance(n_keys, scale_of_terms, d, normalize):
    """Per-row bound on a float32 evaluation: the score sums d terms and
    the output n_keys, each rounding by at most eps · |term|."""
    tol = EPS32 * (n_keys + d) * scale_of_terms
    if normalize:
        tol = tol / np.maximum(n_keys, 1)
    return tol[:, None, None, None]


def _inhibitor_parity(rng, case, *, signed, normalize, window):
    # |v| of the order of the scores z ≈ 1.13·sqrt(d) − 0.5, so that
    # eq. 9/10 do not cancel to zero
    q, kp, vp, tables, lengths = _case_inputs(
        rng, case, v_scale=math.sqrt(case.d))
    out = paged_flash_inhibitor_fwd(q, kp, vp, tables, lengths,
                                    signed=signed, normalize=normalize,
                                    window=window,
                                    pages_per_step=case.pages_per_step,
                                    interpret=True)
    ref, n_keys, scale = _inhibitor_f64(q, kp, vp, tables, lengths,
                                        signed=signed, normalize=normalize,
                                        window=window)
    # the reference is not near zero: a wrong sum would show
    assert np.abs(ref).max() > 0.05 * math.sqrt(case.d)
    err = np.abs(np.asarray(out, np.float64) - ref)
    # float32 sums, then rounded once to the output's dtype
    tol = (_f32_sum_tolerance(n_keys, scale, case.d, normalize)
           + np.abs(ref) * float(jnp.finfo(out.dtype).eps) / 2)
    assert np.all(err <= tol), float((err / tol).max())


@pytest.mark.parametrize(
    "signed,window,normalize,case",
    [pytest.param(s, w, n, BASE, id=f"{s}-{w}-{n}")
     for s in (True, False) for w in (None, 5) for n in (True, False)]
    + [pytest.param(True, c.window, True, c, id=name)
       for name, c in WALK_CASES.items()])
def test_paged_inhibitor_parity_sweep(rng, signed, window, normalize, case):
    _inhibitor_parity(rng, case, signed=signed, normalize=normalize,
                      window=window)


@pytest.mark.parametrize(
    "window,case",
    [pytest.param(w, BASE, id=str(w)) for w in (None, 5)]
    + [pytest.param(c.window, c, id=name)
       for name, c in WALK_CASES.items()])
def test_paged_attention_parity_sweep(rng, window, case):
    q, kp, vp, tables, lengths = _case_inputs(rng, case)
    out = paged_flash_attention_fwd(q, kp, vp, tables, lengths,
                                    window=window,
                                    pages_per_step=case.pages_per_step,
                                    interpret=True)
    ref = kref.paged_flash_attention_ref(q.astype(jnp.float32), kp, vp,
                                         tables, lengths, window=window)
    # a bf16 output is the float32 result rounded to 8 bits
    tol = TOL if out.dtype == jnp.float32 else dict(rtol=2 ** -8, atol=1e-5)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref),
                               **tol)


@pytest.mark.parametrize("pps", [1, 2, 3, 4, 5, 8, 16, None])
def test_pages_per_step_is_semantics_free(rng, pps):
    """Every compute-block size produces the same result — it is a
    launch-configuration knob, not a semantic one.  The 64-token row
    fills its eight-column table; ``None`` is the block the shapes
    derive."""
    kp, vp, tables, lengths = _pool(
        rng, batch=4, pages_per_slot=8, page_size=8, kv_heads=2, d=16,
        lengths=[13, 8, 64, 37])
    q = jnp.asarray(rng.normal(size=(4, 1, 4, 16)).astype(np.float32))
    base = paged_flash_inhibitor_fwd(q, kp, vp, tables, lengths,
                                     pages_per_step=1, interpret=True)
    out = paged_flash_inhibitor_fwd(q, kp, vp, tables, lengths,
                                    pages_per_step=pps, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(base),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("mech", ["inhibitor", "inhibitor_unsigned",
                                  "dotprod"])
def test_paged_pallas_backend_matches_fused_gather(rng, mech):
    """Registry-level parity: the paged_pallas backend ≡ the fused gather
    backend for every registered mechanism, over ragged cursors."""
    kp, vp, tables, lengths = _pool(
        rng, batch=4, pages_per_slot=4, page_size=8, kv_heads=2, d=16,
        lengths=RAGGED_LENGTHS)
    q = jnp.asarray(rng.normal(size=(4, 1, 4, 16)).astype(np.float32))
    m = get_mechanism(mech)
    params = m.make_params(score_scale=None, score_shift=0.5,
                           normalize=True, kv_chunk=64)
    layout = PagedLayout(tables, 8)
    structural = Structural(causal=True, window=None,
                            q_offset=lengths - 1, kv_valid_len=lengths)
    out = execute_plan(ExecutionPlan(mech, "paged_pallas", "test"),
                       q, kp, vp, params=params, structural=structural,
                       paged=layout)
    kj = jnp.arange(tables.shape[1] * 8)[None, :]
    mask = (kj < lengths[:, None])[:, None, None, :]
    ref = execute_plan(ExecutionPlan(mech, "paged", "test"),
                       q, kp, vp, params=params, mask=mask, paged=layout)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), **TOL)


def test_trash_page_garbage_cannot_reach_attendable_positions(rng):
    """Regression (ISSUE 4 satellite): poison the trash page 0 and every
    never-mapped pool page with huge garbage — kernel and gather outputs
    must be unchanged, because those rows sit beyond every cursor."""
    kp, vp, tables, lengths = _pool(
        rng, batch=3, pages_per_slot=4, page_size=8, kv_heads=2, d=16,
        lengths=[13, 8, 1])
    q = jnp.asarray(rng.normal(size=(3, 1, 4, 16)).astype(np.float32))
    mapped = np.unique(np.asarray(tables))
    mapped = mapped[mapped != 0]               # page 0 is never attendable
    poison_rows = np.setdiff1d(np.arange(kp.shape[0]), mapped)
    kp_bad = kp.at[poison_rows].set(1e9)
    vp_bad = vp.at[poison_rows].set(-1e9)
    # also poison the valid pages' tail rows *beyond* each cursor: those
    # slots belong to the row but are past its valid length
    for b, ln in enumerate([13, 8, 1]):
        used = -(-ln // 8)
        last_page = int(np.asarray(tables)[b, used - 1])
        tail = ln - (used - 1) * 8
        if tail < 8:
            kp_bad = kp_bad.at[last_page, :, tail:].set(1e9)
            vp_bad = vp_bad.at[last_page, :, tail:].set(-1e9)

    for fwd, kw in ((paged_flash_inhibitor_fwd, dict(signed=True)),
                    (paged_flash_attention_fwd, {})):
        clean = fwd(q, kp, vp, tables, lengths, interpret=True, **kw)
        poisoned = fwd(q, kp_bad, vp_bad, tables, lengths, interpret=True,
                       **kw)
        np.testing.assert_allclose(np.asarray(poisoned), np.asarray(clean),
                                   rtol=1e-6, atol=1e-6)

    # and through the gather backend (mask must exclude every trash row)
    m = get_mechanism("inhibitor")
    params = m.make_params(score_scale=None, score_shift=0.5,
                           normalize=True, kv_chunk=64)
    layout = PagedLayout(tables, 8)
    kj = jnp.arange(tables.shape[1] * 8)[None, :]
    mask = (kj < lengths[:, None])[:, None, None, :]
    plan = ExecutionPlan("inhibitor", "paged", "test")
    clean = execute_plan(plan, q, kp, vp, params=params, mask=mask,
                         paged=layout)
    poisoned = execute_plan(plan, q, kp_bad, vp_bad, params=params,
                            mask=mask, paged=layout)
    np.testing.assert_allclose(np.asarray(poisoned), np.asarray(clean),
                               rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# kernel registry (kernels/ops.py)
# ---------------------------------------------------------------------------

def test_registry_single_interpret_decision():
    assert isinstance(kops.registry.interpret, bool)
    # cached: repeated reads return the same object decision
    assert kops.registry.interpret == kops.registry.interpret


def test_registry_caches_choice_per_shape(rng):
    kops.registry.tuned.clear()
    kp, vp, tables, lengths = _pool(
        rng, batch=2, pages_per_slot=2, page_size=8, kv_heads=2, d=16,
        lengths=[5, 9])
    q = jnp.asarray(rng.normal(size=(2, 1, 4, 16)).astype(np.float32))
    kops.paged_flash_inhibitor(q, kp, vp, tables, lengths)
    keys = [k for k in kops.registry.tuned if k[0] == "paged"]
    assert len(keys) == 1
    kops.paged_flash_inhibitor(q, kp, vp, tables, lengths)
    assert len([k for k in kops.registry.tuned if k[0] == "paged"]) == 1


def _spy_choose(monkeypatch):
    """Wrap registry.choose, recording every override it is handed."""
    seen = []
    orig = kops.registry.choose

    def spy(family, shape_key, override=None, timer=None):
        seen.append((family, override))
        return orig(family, shape_key, override, timer)

    monkeypatch.setattr(kops.registry, "choose", spy)
    return seen


def test_kernel_choice_override_wins(rng, monkeypatch):
    """An explicit KernelChoice (e.g. from AttentionConfig.kernel_*) is
    handed to the registry verbatim and produces identical numerics."""
    seen = _spy_choose(monkeypatch)
    kp, vp, tables, lengths = _pool(
        rng, batch=2, pages_per_slot=4, page_size=8, kv_heads=2, d=16,
        lengths=[13, 30])
    q = jnp.asarray(rng.normal(size=(2, 1, 4, 16)).astype(np.float32))
    base = kops.paged_flash_inhibitor(q, kp, vp, tables, lengths)
    for pps in (1, 2):
        out = kops.paged_flash_inhibitor(
            q, kp, vp, tables, lengths,
            choice=kops.KernelChoice(pages_per_step=pps))
        np.testing.assert_allclose(np.asarray(out), np.asarray(base),
                                   rtol=1e-6, atol=1e-6)
    overrides = [o for _, o in seen if o is not None]
    assert [o.pages_per_step for o in overrides] == [1, 2]


def test_attention_config_kernel_override_reaches_registry(rng,
                                                           monkeypatch):
    """AttentionConfig.kernel_* fields flow through MechanismParams into
    the kernel registry (block sizes are config, not module constants) —
    asserted on the override the registry actually receives, since block
    sizes are numerics-invariant launch knobs."""
    from repro.core.attention import (AttentionConfig, apply_attention,
                                      init_attention)
    from repro.nn.module import unbox

    seen = _spy_choose(monkeypatch)
    x = jnp.asarray(rng.normal(size=(2, 16, 32)).astype(np.float32))
    outs = []
    for bq in (None, 8):
        cfg = AttentionConfig(mechanism="inhibitor", num_heads=4,
                              num_kv_heads=2, head_dim=8, backend="pallas",
                              kernel_block_q=bq, kernel_block_k=8,
                              kernel_sub_k=4)
        params = unbox(init_attention(jax.random.PRNGKey(0), cfg, 32))
        y, _ = apply_attention(params, cfg, x)
        outs.append(np.asarray(y))
    np.testing.assert_allclose(outs[0], outs[1], rtol=1e-4, atol=1e-5)
    overrides = [o for fam, o in seen if fam == "inhibitor"]
    assert [ (o.block_q, o.block_k, o.sub_k) for o in overrides ] \
        == [(None, 8, 4), (8, 8, 4)]


def test_kernel_choice_merge_semantics():
    base = kops.KernelChoice(64, 128, 16, 4)
    partial = kops.KernelChoice(block_k=256)
    merged = partial.merge_onto(base)
    assert dataclasses.astuple(merged) == (64, 256, 16, 4)
    assert kops.KernelChoice().empty
    assert not partial.empty


def test_shared_pages_across_rows_read_identically(rng):
    """Prefix-cache invariant (DESIGN.md §11): two rows whose block
    tables reference the *same* physical pages (a mounted shared prefix)
    must read them identically — the paged kernels and the gather
    backend tolerate multiply-referenced table entries, because a table
    entry is just an index into the pool.

    Construction: rows 0 and 1 share their first two physical pages
    (16 tokens of common prefix) and diverge afterwards; row 2 is
    unrelated.  The check is against a dense per-row gather of each
    row's logical view — if any path special-cased "pages are disjoint",
    the shared rows would read garbage.
    """
    heads, kv_heads, d, ps = 4, 2, 16, 8
    batch, pages_per_slot = 3, 4
    num_pages = batch * pages_per_slot + 1
    kp = rng.normal(size=(num_pages, ps, kv_heads, d)).astype(np.float32)
    vp = rng.normal(size=(num_pages, ps, kv_heads, d)).astype(np.float32)
    kp, vp = kp.transpose(0, 2, 1, 3), vp.transpose(0, 2, 1, 3)
    lengths = np.asarray([21, 18, 13], np.int32)
    tables = np.zeros((batch, pages_per_slot), np.int32)
    tables[0, :3] = [1, 2, 3]       # rows 0/1 share physical pages 1, 2
    tables[1, :3] = [1, 2, 4]       # (the mounted prefix), then diverge
    tables[2, :2] = [5, 6]
    kp, vp = jnp.asarray(kp), jnp.asarray(vp)
    tables_j, lengths_j = jnp.asarray(tables), jnp.asarray(lengths)
    q = jnp.asarray(rng.normal(size=(batch, 1, heads, d)).astype(np.float32))

    # dense oracle: gather each row's logical view and run the reference
    def dense_view(pool):
        arr = np.asarray(pool)
        out = np.stack([arr[tables[b]].transpose(0, 2, 1, 3)
                        .reshape(-1, kv_heads, d) for b in range(batch)])
        return jnp.asarray(out)

    kd, vd = dense_view(kp), dense_view(vp)
    kj = jnp.arange(pages_per_slot * ps)[None, :]
    mask = (kj < lengths_j[:, None])[:, None, None, :]

    m = get_mechanism("inhibitor")
    params = m.make_params(score_scale=None, score_shift=0.5,
                           normalize=True, kv_chunk=64)
    oracle = execute_plan(ExecutionPlan("inhibitor", "fused", "test"),
                          q, kd, vd, params=params, mask=mask)

    layout = PagedLayout(tables_j, ps)
    structural = Structural(causal=True, window=None,
                            q_offset=lengths_j - 1, kv_valid_len=lengths_j)
    out_kernel = execute_plan(
        ExecutionPlan("inhibitor", "paged_pallas", "test"),
        q, kp, vp, params=params, structural=structural, paged=layout)
    out_gather = execute_plan(
        ExecutionPlan("inhibitor", "paged", "test"),
        q, kp, vp, params=params, mask=mask, paged=layout)
    np.testing.assert_allclose(np.asarray(out_kernel), np.asarray(oracle),
                               **TOL)
    np.testing.assert_allclose(np.asarray(out_gather), np.asarray(oracle),
                               **TOL)


def test_native_platform_declarations():
    """Every kernel family declares where its Pallas body lowers
    natively; all four are TPU-only today (scalar-prefetch grids have no
    Triton equivalent) — a future GPU body flips one declaration."""
    assert set(kops.NATIVE_PLATFORMS) == {"inhibitor", "flash", "paged",
                                          "wkv6"}
    for plats in kops.NATIVE_PLATFORMS.values():
        assert "tpu" in plats


def test_interpret_for_tracks_family_declaration(monkeypatch):
    """interpret_for is per-family and platform-derived: native on TPU,
    interpret elsewhere; the _interpret test escape hatch overrides every
    family at once."""
    monkeypatch.setattr(kops.registry, "_interpret", None)
    monkeypatch.setattr(kops.registry, "_platform", "tpu")
    assert not kops.registry.interpret_for("paged")
    assert not kops.registry.interpret
    monkeypatch.setattr(kops.registry, "_platform", "cuda")
    assert kops.registry.interpret_for("paged")     # no Triton body yet
    assert kops.registry.interpret
    monkeypatch.setattr(kops.registry, "_interpret", False)
    assert not kops.registry.interpret_for("paged")


def test_choose_records_decision_provenance():
    """registry.decisions records which launch config won and why:
    trace-time resolutions stay unpinned, concrete resolutions record
    timed/default-interpret by platform, overrides always win."""
    r = kops.KernelRegistry()
    r._platform = "cpu"
    key = ("probe", 4, 1, 4, 2, 16)
    full = ("paged",) + key

    got = r.choose("paged", key)
    assert r.decisions[full]["source"] == "default-trace"
    assert full not in r.tuned          # trace-time never pins the cache

    got = r.choose("paged", key, timer=lambda c: 0.0)
    d = r.decisions[full]
    assert d["source"] == "default-interpret"       # cpu: nothing to time
    assert d["platform"] == "cpu" and d["native"] is False
    assert full in r.tuned

    ov = kops.KernelChoice(pages_per_step=2)
    got = r.choose("paged", key, override=ov)
    assert got.pages_per_step == 2
    assert r.decisions[full]["source"] == "override"

    # native platform: the timer actually ranks candidates and records
    # a timed decision with the costmodel priors alongside
    rt = kops.KernelRegistry()
    rt._platform = "tpu"
    rt.choose("paged", key, timer=lambda c: float(c.pages_per_step or 1))
    dt = rt.decisions[("paged",) + key]
    assert dt["source"] == "timed" and dt["native"] is True
    assert ("paged",) + key in rt.priors


def test_autotune_logs_dropped_candidates_and_raises_when_all_fail(
        caplog, monkeypatch):
    """A candidate that raises during timing drops out with its error on
    record; when every candidate fails, the first error propagates
    instead of silently pinning a default that was never shown to run.
    (The paged family ships one candidate, the block its shapes derive;
    the race is run over four.)"""
    monkeypatch.setitem(kops.CANDIDATES, "paged", tuple(
        kops.KernelChoice(pages_per_step=n) for n in (4, 1, 2, 8)))
    r = kops.KernelRegistry()
    r._platform = "tpu"
    key = ("probe", 4, 16, 9, 3, 64)

    def flaky(c):
        if c.pages_per_step != 2:
            raise RuntimeError(f"vmem overflow pps={c.pages_per_step}")
        return 1.0

    with caplog.at_level("WARNING", logger="repro.kernels"):
        got = r.choose("paged", key, timer=flaky)
    assert got.pages_per_step == 2
    dropped = [m for m in caplog.messages if "dropped" in m]
    assert len(dropped) == len(kops.CANDIDATES["paged"]) - 1
    assert all("vmem overflow" in m for m in dropped)

    def broken(c):
        raise RuntimeError(f"lowering failed pps={c.pages_per_step}")

    fresh = kops.KernelRegistry()
    fresh._platform = "tpu"
    with pytest.raises(RuntimeError, match="lowering failed"):
        fresh.choose("paged", key, timer=broken)
    assert ("paged",) + key not in fresh.tuned


def test_host_platform_probe_errors_propagate(monkeypatch):
    """A failed device probe is an error, not a quiet "cpu": reading it
    as cpu would put a chip host's kernels on the interpret path."""
    def no_backend():
        raise RuntimeError("backend init failed")

    monkeypatch.setattr(kops.jax, "devices", no_backend)
    with pytest.raises(RuntimeError, match="backend init failed"):
        _ = kops.KernelRegistry().platform
