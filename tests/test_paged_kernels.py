"""Paged decode kernels (kernels/paged.py) + the kernel registry
(kernels/ops.py): interpret-mode parity sweeps against the gather
references, the fused paged backend, trash-page isolation, and the
registry's choice/override plumbing (DESIGN.md §10).

The sweep covers the decode shapes the serve engine actually produces:
GQA groups, sliding windows, ragged per-row cursors, cursors that
straddle a page boundary / land exactly on one / sit at a single token,
and the ``normalize`` flag.
"""

import dataclasses

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


def _pool(rng, *, batch, pages_per_slot, page_size, kv_heads, d,
          lengths):
    """A ragged head-major paged pool (num_pages, kv_heads, page_size, d):
    per-row non-contiguous physical pages, trash page 0 for every
    unmapped table entry (the engine's layout)."""
    num_pages = batch * pages_per_slot + 1
    # drawn token-major, stored head-major: the same KV content per
    # (page, offset, head) as a token-major pool drawn from this rng
    kp = rng.normal(size=(num_pages, page_size, kv_heads, d))
    vp = rng.normal(size=(num_pages, page_size, kv_heads, d))
    kp, vp = kp.transpose(0, 2, 1, 3), vp.transpose(0, 2, 1, 3)
    perm = rng.permutation(np.arange(1, num_pages))
    tables = np.zeros((batch, pages_per_slot), np.int32)
    nxt = 0
    for b, ln in enumerate(lengths):
        used = -(-int(ln) // page_size)
        tables[b, :used] = perm[nxt:nxt + used]
        nxt += used
    return (jnp.asarray(kp.astype(np.float32)),
            jnp.asarray(vp.astype(np.float32)), jnp.asarray(tables),
            jnp.asarray(np.asarray(lengths, np.int32)))


# page_size 8: 13 straddles a boundary, 8 lands exactly on one, 1 is a
# single token, 24 fills three pages
RAGGED_LENGTHS = [13, 8, 1, 24]


@pytest.mark.parametrize("signed", [True, False])
@pytest.mark.parametrize("window", [None, 5])
@pytest.mark.parametrize("normalize", [True, False])
def test_paged_inhibitor_parity_sweep(rng, signed, window, normalize):
    heads, kv_heads, d, ps = 4, 2, 16, 8       # GQA group of 2
    kp, vp, tables, lengths = _pool(
        rng, batch=4, pages_per_slot=4, page_size=ps, kv_heads=kv_heads,
        d=d, lengths=RAGGED_LENGTHS)
    q = jnp.asarray(rng.normal(size=(4, 1, heads, d)).astype(np.float32))
    out = paged_flash_inhibitor_fwd(q, kp, vp, tables, lengths,
                                    signed=signed, normalize=normalize,
                                    window=window, interpret=True)
    ref = kref.paged_flash_inhibitor_ref(q, kp, vp, tables, lengths,
                                         signed=signed, normalize=normalize,
                                         window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), **TOL)


@pytest.mark.parametrize("window", [None, 5])
def test_paged_attention_parity_sweep(rng, window):
    heads, kv_heads, d, ps = 4, 2, 16, 8
    kp, vp, tables, lengths = _pool(
        rng, batch=4, pages_per_slot=4, page_size=ps, kv_heads=kv_heads,
        d=d, lengths=RAGGED_LENGTHS)
    q = jnp.asarray(rng.normal(size=(4, 1, heads, d)).astype(np.float32))
    out = paged_flash_attention_fwd(q, kp, vp, tables, lengths,
                                    window=window, interpret=True)
    ref = kref.paged_flash_attention_ref(q, kp, vp, tables, lengths,
                                         window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), **TOL)


@pytest.mark.parametrize("pps", [1, 2, 3, 4])
def test_pages_per_step_is_semantics_free(rng, pps):
    """Every pages_per_step staging produces the same result — it is a
    launch-configuration knob, not a semantic one."""
    kp, vp, tables, lengths = _pool(
        rng, batch=3, pages_per_slot=4, page_size=8, kv_heads=2, d=16,
        lengths=[13, 8, 32])
    q = jnp.asarray(rng.normal(size=(3, 1, 4, 16)).astype(np.float32))
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


def test_autotune_logs_dropped_candidates_and_raises_when_all_fail(caplog):
    """A candidate that raises during timing drops out with its error on
    record; when every candidate fails, the first error propagates
    instead of silently pinning a default that was never shown to run."""
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
