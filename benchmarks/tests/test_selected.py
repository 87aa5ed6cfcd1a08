"""The comparison of kind ``selected`` (``lib/selected.py``) on the CPU
at a tiny size: its statistics against a brute-force version, the sound
stand-in and each defect it has to catch, and ``run.py`` end to end on
a program with a selecting attention planted from outside, alone and
composed with routing. Since PR 56 the same in UNITS: a selection by
blocks of keys, two selections a layer, units the model's rule forces
(the block stand-in, its defects, the block tap), and the path of one
key a unit held bit for bit to what it computed before."""

import json
import os
import sys

import numpy as np
import pytest

from benchmarks.tests import defects
from benchmarks.tests.rehearse_selected import TINY as TINY_WIDTHS
from benchmarks.tests.rehearse_selected import TINY_BLOCKS
from benchmarks.tests.test_rehearsal import (
    ROOT, ROUTED_CHECKS, ROUTED_SEED, TINY, TINY_MOE, _events, _run_patched,
)

TOLERANCES = (4e-2, 2.5e-2, 2e-4)  # the runner's LOGIT, LOGIT_RMS, LOSS
STANDIN = dict(TINY_WIDTHS, n_layer=2)  # the rehearsal's ``--tiny`` size
SELECTED_CHECKS = [
    "selection_valid", "selection_regret", "selection_moved",
    "logits_vs_reference", "logits_rms_vs_reference", "loss_vs_reference",
    "indexer_loss_vs_reference", "loss_vs_free_reference",
]


# ---- the statistics --------------------------------------------------------

def _brute_force(scores, chosen, k, forced=None):
    """``selected.selection_stats`` row by row, in numpy."""
    names = ("regret", "gap", "moved") + (
        () if forced is None else ("forced_missing",)
    )
    out = {name: np.zeros(scores.shape[:-1]) for name in names}
    for at in np.ndindex(*scores.shape[:-1]):
        row, picked = scores[at], chosen[at]
        free, room = np.isfinite(row), k
        if forced is not None:
            held = forced[at] & free
            out["forced_missing"][at] = np.sum(held & ~picked)
            free, picked = free & ~held, picked & ~held
            room = max(k - held.sum(), 0)
        seen = row[free]
        size = min(len(seen), room)
        if size == 0:  # nothing left to choose
            out["gap"][at] = np.nan
            continue
        ranked = np.sort(seen)[::-1]
        kth, std = ranked[size - 1], seen.std()
        worst = row[picked].min() if picked.any() else np.inf
        out["regret"][at] = max(0.0, kth - worst) / std if std > 0 else 0.0
        out["gap"][at] = (
            (kth - ranked[size]) / std if size < len(seen) else np.nan
        )
        out["moved"][at] = np.sum(row[picked] < kth) / size
    return out


def _hand_built():
    """8 queries over 8 keys, k = 3: rows 0..2 have no choice (t < k);
    row 5 holds an exact tie at the k-th place."""
    rng = np.random.default_rng(7)
    scores = rng.normal(size=(1, 8, 8)).astype(np.float32)
    scores[0, 5, :6] = [0.5, 2.0, 1.0, 1.0, -1.0, 3.0]  # 3.0, 2.0, then a tie
    scores[0][np.triu_indices(8, 1)] = -np.inf
    return scores


def test_top_selection_breaks_ties_to_the_lower_key():
    from benchmarks.tests import sparse_plain, sparse_standin

    scores = _hand_built()
    want = np.zeros(scores.shape, bool)
    for t in range(8):
        order = sorted(range(t + 1), key=lambda s: (-scores[0, t, s], s))
        want[0, t, order[:3]] = True
    assert want[0, 5].tolist() == [0, 1, 1, 0, 0, 1, 0, 0]  # key 2, not 3
    assert want[0, 1].tolist() == [1, 1, 0, 0, 0, 0, 0, 0]  # t < k: all seen
    got = np.asarray(sparse_plain.top_selection(scores, 3))
    assert (got == want).all()
    # the stand-in ranks by two argsorts: another route to the same set
    qpos = np.arange(8)[:, None]
    assert (np.asarray(sparse_standin._select(scores, 3, qpos)) == want).all()


def test_selection_stats_match_brute_force():
    from benchmarks.lib import selected
    from benchmarks.tests import sparse_plain

    scores = _hand_built()
    own = np.asarray(sparse_plain.top_selection(scores, 3))
    stats = {k: np.asarray(v) for k, v in
             selected.selection_stats(scores, own, 3).items()}
    # the reference's own selection costs nothing, ties included
    assert (stats["regret"] == 0).all() and (stats["moved"] == 0).all()
    assert np.isnan(stats["gap"][0, :3]).all()  # t < k: no choice, no gap
    assert stats["gap"][0, 5] == 0.0  # the tie
    assert (stats["gap"][0, 3:] >= 0).all()
    # the other key of the tie is as good: regret 0, nothing moved
    tie = own.copy()
    tie[0, 5, 2], tie[0, 5, 3] = False, True
    # and a selection that is off: the last three keys of every row
    recent = np.zeros_like(own)
    for t in range(8):
        recent[0, t, max(0, t - 2): t + 1] = True
    for chosen in (own, tie, recent):
        got = selected.selection_stats(scores, chosen, 3)
        want = _brute_force(scores, chosen, 3)
        for name in want:
            np.testing.assert_allclose(
                np.asarray(got[name]), want[name], rtol=1e-5, atol=1e-6,
                err_msg=name,
            )
    assert np.asarray(
        selected.selection_stats(scores, tie, 3)["regret"]
    )[0, 5] == 0.0
    off = selected.selection_stats(scores, recent, 3)
    assert float(np.max(off["regret"])) > 0.5
    assert (np.asarray(off["moved"])[0, :3] == 0).all()


def _unit_scores():
    """10 queries over 10 units, one a query: rows 0..2 see fewer units
    than a k of 4, and row 6 holds an exact tie (units 2 and 3)."""
    rng = np.random.default_rng(56)
    scores = rng.normal(size=(1, 10, 10)).astype(np.float32)
    scores[0, 6, :7] = [0.1, 1.5, 0.7, 0.7, -0.3, 2.0, 0.9]
    scores[0][np.triu_indices(10, 1)] = -np.inf
    return scores


def _forced_rule(n, first, local):
    """bool [1, n, n]: the first ``first`` units and the ``local``
    nearest, among those a query sees."""
    t, u = np.arange(n)[:, None], np.arange(n)[None, :]
    return (((u < first) | (u > t - local)) & (u <= t))[None]


@pytest.mark.parametrize("first,local", [(1, 2), (0, 1), (2, 3)])
def test_selection_stats_with_forced_units_match_brute_force(first, local):
    from benchmarks.lib import selected
    from benchmarks.tests import block_plain

    scores, k = _unit_scores(), 4
    forced = _forced_rule(10, first, local)
    own = np.asarray(block_plain.top_units(scores, forced, k))
    # rows 0..2 see fewer units than k; with (2, 3) the forced units
    # EXCEED k from row 4 on: nothing is left to choose
    assert own.sum(-1)[0].tolist() == [
        max(min(t + 1, k), int(forced[0, t].sum())) for t in range(10)
    ]
    got = {n: np.asarray(v) for n, v in
           selected.selection_stats(scores, own, k, forced).items()}
    assert (got["forced_missing"] == 0).all()
    assert (got["regret"] == 0).all() and (got["moved"] == 0).all()
    if (first, local) == (2, 3):
        assert np.isnan(got["gap"][0, 4:]).all()
    # a forced unit traded for a free one; the worst free unit for the
    # best; the program's set shifted by one place
    traded, worst, shifted = own.copy(), own.copy(), np.roll(own, 1, axis=-1)
    traded[0, 9, 9], traded[0, 9, np.argmin(np.where(
        own[0, 9], np.inf, scores[0, 9]))] = False, True
    free = own & ~forced
    for t in range(10):
        if free[0, t].any() and (~own[0, t] & np.isfinite(scores[0, t])).any():
            best = np.argmax(np.where(free[0, t], scores[0, t], -np.inf))
            low = np.argmin(np.where(
                ~own[0, t] & np.isfinite(scores[0, t]), scores[0, t], np.inf
            ))
            worst[0, t, best], worst[0, t, low] = False, True
    shifted &= np.isfinite(scores)
    for chosen in (own, traded, worst, shifted):
        got = selected.selection_stats(scores, chosen, k, forced)
        want = _brute_force(scores, chosen, k, forced)
        assert set(got) == set(want)
        for name in want:
            np.testing.assert_allclose(
                np.asarray(got[name]), want[name], rtol=1e-5, atol=1e-6,
                err_msg=name,
            )
    missing = np.asarray(
        selected.selection_stats(scores, traded, k, forced)["forced_missing"]
    )
    assert missing.sum() == missing[0, 9] == 1
    if (first, local) != (2, 3):
        off = selected.selection_stats(scores, worst, k, forced)
        assert float(np.max(off["regret"])) > 0.5


def test_a_tie_among_the_free_units_costs_nothing():
    from benchmarks.lib import selected
    from benchmarks.tests import block_plain

    scores, k = _unit_scores(), 4
    forced = _forced_rule(10, 1, 1)  # units 0 and t: two free places
    own = np.asarray(block_plain.top_units(scores, forced, k))
    # row 6: forced 0 and 6; free 1..5 = 1.5, .7, .7, -.3, 2.0: 5 and 1
    assert own[0, 6].tolist() == [1, 1, 0, 0, 0, 1, 1, 0, 0, 0]
    forced3 = _forced_rule(10, 1, 0) | (np.arange(10) == 6)[None, None, :] & (
        np.arange(10)[None, :, None] == 6
    )
    own3 = np.asarray(block_plain.top_units(scores, forced3, 5))
    # five places, two forced: 2.0, 1.5 and the LOWER unit of the tie
    assert own3[0, 6].tolist() == [1, 1, 1, 0, 0, 1, 1, 0, 0, 0]
    tie = own3.copy()
    tie[0, 6, 2], tie[0, 6, 3] = False, True
    for chosen in (own3, tie):
        got = selected.selection_stats(scores, chosen, 5, forced3)
        assert float(got["regret"][0, 6]) == 0.0
        assert float(got["moved"][0, 6]) == 0.0
        assert float(got["gap"][0, 6]) == 0.0
    # the stand-in ranks by two argsorts: another route to the same set
    from benchmarks.tests import block_standin

    qpos = np.arange(10)
    for rule, size in ((forced, k), (forced3, 5)):
        ranked = block_standin._select_units(scores[None], rule[0], size, qpos)
        want = block_plain.top_units(scores, rule, size)
        assert (np.asarray(ranked[0]) == np.asarray(want)).all()


def _parent_selection_stats(scores, chosen, k):
    """``lib/selected.selection_stats`` as it stood before PR 56, line
    for line: what the path of one key a unit is held to, bit for bit."""
    import jax.numpy as jnp

    visible = jnp.isfinite(scores)
    n_visible = jnp.sum(visible, axis=-1)
    size = jnp.minimum(n_visible, k)  # k'
    ranked = jnp.sort(scores, axis=-1, descending=True)

    def at(index):
        index = jnp.clip(index, 0, scores.shape[-1] - 1)
        return jnp.take_along_axis(ranked, index[..., None], axis=-1)[..., 0]

    kth, after = at(size - 1), at(size)
    mean = jnp.sum(jnp.where(visible, scores, 0.0), -1) / n_visible
    dev = jnp.where(visible, scores - mean[..., None], 0.0)
    std = jnp.sqrt(jnp.sum(dev * dev, -1) / n_visible)
    worst = jnp.min(jnp.where(chosen, scores, jnp.inf), axis=-1)
    regret = jnp.where(std > 0, jnp.maximum(kth - worst, 0.0) / std, 0.0)
    gap = jnp.where(size < n_visible, (kth - after) / std, jnp.nan)
    under = chosen & (scores < kth[..., None])
    return {
        "regret": regret,
        "gap": gap,
        "moved": jnp.sum(under, axis=-1) / size,
    }


def test_one_key_a_unit_is_the_parent_bit_for_bit(monkeypatch):
    """The key stand-in's whole judgement, every number of its record,
    with this PR's ``selection_stats`` and with the parent's in its
    place; and the two functions on arrays of the stand-in's making,
    jitted as the reference jits them."""
    import jax

    from benchmarks.lib import selected
    from benchmarks.tests import sparse_plain, sparse_standin

    rng = np.random.default_rng(5600)
    scores = rng.normal(size=(2, 96, 96)).astype(np.float32)
    scores[:, np.triu_indices(96, 1)[0], np.triu_indices(96, 1)[1]] = -np.inf
    rounded = np.asarray(
        jax.numpy.asarray(scores).astype(jax.numpy.bfloat16), np.float32
    )
    chosen = np.asarray(
        sparse_standin._select(rounded, 16, np.arange(96)[:, None])
    )
    new = jax.jit(lambda s, c: selected.selection_stats(s, c, 16))(
        scores, chosen
    )
    old = jax.jit(lambda s, c: _parent_selection_stats(s, c, 16))(
        scores, chosen
    )
    assert set(new) == set(old)
    for name in old:
        a, b = np.asarray(new[name]), np.asarray(old[name])
        assert a.dtype == b.dtype and a.tobytes() == b.tobytes(), name
    assert float(np.max(np.asarray(new["regret"]))) > 0  # keys did move

    checks, record = _judge(monkeypatch)
    monkeypatch.setattr(
        sparse_plain, "selection_stats", _parent_selection_stats
    )
    parent_checks, parent_record = _judge(monkeypatch)
    assert checks == parent_checks
    assert record == parent_record and "select_rows" not in record


@pytest.mark.parametrize("block", [1, 64])
@pytest.mark.parametrize("groups", [1, 2])
def test_validity_in_units(block, groups):
    """Query t sees units 0 .. t // block: its row holds
    min(t // block + 1, k) of them and none above; G rows a layer are G
    selections, each judged."""
    from benchmarks.lib import selected

    layers, k, n_units = 2, 3, 8
    s = n_units * block
    own = (np.arange(s) // block)[:, None]
    unit = np.arange(n_units)[None, :]
    recent = (unit <= own) & (unit > own - k)
    mask = np.broadcast_to(recent, (layers * groups, 1, s, n_units)).copy()
    assert selected.selection_faults(mask, k, block) == 0
    last = layers * groups - 1
    t = 5 * block + block // 2  # a query inside block 5
    short = mask.copy()
    short[last, 0, t, 5] = False
    assert selected.selection_faults(short, k, block) == 1
    ahead = mask.copy()  # as many units as there should be, one ahead
    ahead[0, 0, t, 3], ahead[0, 0, t, 6] = False, True
    assert selected.selection_faults(ahead, k, block) == 1
    both = short & ahead
    assert selected.selection_faults(both, k, block) == 1 + (last > 0)
    # the query's own block is a unit it sees, from its first key on
    first = mask.copy()
    first[0, 0, 5 * block, 5] = False
    assert selected.selection_faults(first, k, block) == 1
    with pytest.raises(ValueError, match="S / %d" % block):
        selected.selection_faults(mask[..., :-1], k, block)
    if block > 1:  # keys handed over where units were asked for
        keys = np.zeros((layers, 1, s, s), bool)
        with pytest.raises(ValueError, match="S / 64"):
            selected.selection_faults(keys, k, block)


def test_rows_of_two_groups_are_summarised_row_by_row():
    import jax.numpy as jnp

    from benchmarks.lib import selected

    # 2 layers x 2 groups, B 1, S 4: regret only in (layer 1, group 0)
    regret = np.zeros((4, 1, 4), np.float32)
    regret[2, 0, 3] = 0.25
    gap = np.full((4, 1, 4), 0.1, np.float32)
    moved = np.zeros((4, 1, 4), np.float32)
    moved[1, 0, :] = 0.5
    missing = np.zeros((4, 1, 4), np.int32)
    missing[3, 0, 1] = 2
    stats = {"regret": regret, "gap": gap, "moved": moved}
    as_jax = lambda d: {k: jnp.asarray(v) for k, v in d.items()}
    got = selected.selection_summary(as_jax(stats))
    assert "select_forced_missing" not in got
    assert np.asarray(got["select_regret_max_by_layer"]).tolist() == [
        0, 0, 0.25, 0
    ]
    assert np.asarray(got["select_moved_by_layer"]).tolist() == [0, 0.5, 0, 0]
    assert float(got["select_moved_max"]) == 0.5
    got = selected.selection_summary(
        as_jax(dict(stats, forced_missing=missing))
    )
    assert int(got["select_forced_missing"]) == 2


@pytest.mark.parametrize("fault", ["short", "future", "long"])
def test_rows_that_are_no_selection_are_counted(fault):
    from benchmarks.lib import selected

    s, k = 16, 4
    t = np.arange(s)
    mask = (t[None, :] <= t[:, None]) & (t[None, :] > t[:, None] - k)
    mask = np.broadcast_to(mask, (2, 1, s, s)).copy()
    assert selected.selection_faults(mask, k) == 0
    if fault == "short":
        mask[1, 0, 9, 9] = False
    elif fault == "long":
        mask[0, 0, 12, 0] = True
    else:  # as many keys as there should be, one of them ahead
        mask[0, 0, 5, 5], mask[0, 0, 5, 6] = False, True
    assert selected.selection_faults(mask, k) == 1
    with pytest.raises(ValueError):
        selected.selection_faults(mask.astype(np.int32), k)


# ---- the stand-in and its defects ------------------------------------------

def _judged(standin, plain, sizes, seq, seed):
    """A stand-in at ``sizes`` judged against its plain reference on two
    seeded rows of ``seq`` tokens: (checks by name, the record)."""
    import jax
    import jax.numpy as jnp

    from benchmarks.lib.watch import synthetic_batch

    params = standin.init(jax.random.key(seed % 2**31), sizes)
    batch = {
        k: jnp.asarray(v)
        for k, v in synthetic_batch(seed, 0, 2, seq, 512).items()
    }
    checks, record = standin.judge(plain, params, batch, sizes, 64, TOLERANCES)
    return {name: rest for name, *rest in checks}, record


def _judge(monkeypatch, defect=None, seed=3600001001):
    from benchmarks.tests import sparse_plain, sparse_standin

    if defect:
        defects.SELECTED_INJECT[defect](monkeypatch.setattr)
    sizes = dict(sparse_standin.KEYE_WIDTHS, **STANDIN)
    return _judged(sparse_standin, sparse_plain, sizes, 128, seed)


def test_sound_standin_passes(monkeypatch):
    checks, record = _judge(monkeypatch)
    assert list(checks) == SELECTED_CHECKS
    assert all(ok for ok, _v, _l in checks.values()), checks
    assert len(record["select_moved_by_layer"]) == STANDIN["n_layer"]
    assert len(record["select_regret_max_by_layer"]) == STANDIN["n_layer"]
    assert 0 < record["select_regret_max"] <= record["select_regret_tol"]
    assert record["select_gap_median"] > 0
    # what ``dense`` would have read is recorded beside the forced errors:
    # moved keys make it many times the rounding under one selection
    assert record["logit_rms"] > 4 * record["forced_logit_rms"]
    assert set(record["reference_terms"]) == {"indexer_loss"}


@pytest.mark.parametrize("defect", sorted(defects.SELECTED_INJECT))
def test_selected_comparison_catches(monkeypatch, defect):
    named = defects.SELECTED_CAUGHT_BY[defect]
    if not named:
        # written down as passing: it reads several times what the sound
        # stand-in reads, and still under both limits, as on the chip
        sound, _record = _judge(monkeypatch)
    checks, _record = _judge(monkeypatch, defect)
    failed = {name for name, (ok, _v, _l) in checks.items() if not ok}
    if not named:
        for name, times in (("selection_regret", 3), ("selection_moved", 2)):
            assert checks[name][0], (defect, checks)
            assert checks[name][1] > times * sound[name][1], (defect, checks)
        return
    assert failed & set(named), (defect, checks)
    if "selection_valid" in named:
        # what names no selection is not forced on the reference
        assert list(checks) == ["selection_valid", "loss_vs_free_reference"]
    else:
        assert checks["selection_valid"][0]


# ---- the block stand-in and its defects ------------------------------------

BLOCK_STANDIN = dict(TINY_BLOCKS, n_layer=2)  # the rehearsal's ``--tiny`` size
BLOCK_CHECKS = [
    "selection_valid", "selection_forced", "selection_regret",
    "selection_moved", "logits_vs_reference", "logits_rms_vs_reference",
    "loss_vs_reference", "loss_vs_free_reference",
]


def _judge_blocks(monkeypatch, defect=None, seed=5600001001, **sizes):
    from benchmarks.tests import block_plain, block_standin

    if defect:
        defects.BLOCK_INJECT[defect](monkeypatch.setattr)
    sizes = dict(block_standin.SALA_WIDTHS, **BLOCK_STANDIN, **sizes)
    return _judged(block_standin, block_plain, sizes, 256, seed)


@pytest.mark.parametrize("groups", [2, 1])
def test_sound_block_standin_passes(monkeypatch, groups):
    """6 blocks of 16 keys a query, 3 of them forced, a selection a KV
    head (and, with one group, one for all heads)."""
    checks, record = _judge_blocks(monkeypatch, select_groups=groups)
    assert list(checks) == BLOCK_CHECKS
    assert all(ok for ok, _v, _l in checks.values()), checks
    rows = BLOCK_STANDIN["n_layer"] * groups  # a row a selection
    assert len(record["select_moved_by_layer"]) == rows
    assert len(record["select_regret_max_by_layer"]) == rows
    assert record["select_rows"] == {
        "block": 16, "groups": groups, "order": "layer-major, group-minor",
    }
    assert 0 < record["select_regret_max"] <= record["select_regret_tol"]
    assert record["select_forced_missing"] == 0
    assert record["select_gap_median"] > 0
    assert record["reference_terms"] == {}  # no indexer, no term to align


def test_plain_and_standin_select_alike_in_float32():
    """The two routes to a block's score (an overlap matrix; strided
    slices of the padded row) and to the selection (the k-th value; a
    rank) on the same float32 numbers give the same units."""
    import jax.numpy as jnp

    from benchmarks.tests import block_plain, block_standin

    sizes = dict(block_standin.SALA_WIDTHS, **BLOCK_STANDIN)
    rng = np.random.default_rng(56)
    q = jnp.asarray(rng.normal(size=(1, 256, 4, 32)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 256, 2, 32)), jnp.float32)
    qpos, n_units = jnp.arange(256), 16
    pooled = block_plain.pooled_keys(k, 8, 4)
    np.testing.assert_allclose(
        np.asarray(block_standin._pooled_keys(k, 8, 4)), np.asarray(pooled),
        rtol=1e-6, atol=1e-6,
    )
    plain = np.asarray(block_plain.unit_scores(q, pooled, qpos, sizes, n_units))
    standin = np.asarray(
        block_standin._unit_scores(q, pooled, qpos, sizes, n_units)
    )
    assert (np.isfinite(plain) == np.isfinite(standin)).all()
    seen = np.isfinite(plain)
    np.testing.assert_allclose(standin[seen], plain[seen], rtol=1e-5, atol=1e-7)
    # a pooled key is scored once its window has ended: query 6 (of
    # block 0) scores nothing yet, and its one unit reads 0
    assert (plain[0, :, 6, 0] == 0).all() and (plain[0, :, 7, 0] > 0).all()
    forced = block_plain.forced_units(qpos, n_units, sizes)
    assert (
        np.asarray(block_standin._forced_units(qpos, n_units, sizes))
        == np.asarray(forced)
    ).all()
    # query 100, block 6: the first block and the local window's two
    assert np.flatnonzero(np.asarray(forced)[100]).tolist() == [0, 5, 6]
    want = np.asarray(block_plain.top_units(plain, forced, 6))
    got = np.asarray(block_standin._select_units(plain, forced, 6, qpos))
    assert (got == want).all()
    assert want.sum(-1)[0, 0].tolist() == [
        min(t // 16 + 1, 6) for t in range(256)
    ]


@pytest.mark.parametrize("defect", sorted(defects.BLOCK_INJECT))
def test_block_selection_catches(monkeypatch, defect):
    named = defects.BLOCK_CAUGHT_BY[defect]
    if named is None:
        sound, _record = _judge_blocks(monkeypatch)
    checks, _record = _judge_blocks(monkeypatch, defect)
    failed = {name for name, (ok, _v, _l) in checks.items() if not ok}
    if named is None:
        # recorded, not required: the 8-bit pooled-key product reads many
        # times the sound stand-in, whichever side of the limits it falls
        assert checks["selection_valid"][0] and checks["selection_forced"][0]
        assert (
            checks["selection_regret"][1] > 3 * sound["selection_regret"][1]
        )
        return
    assert failed & set(named), (defect, checks)
    if "selection_valid" in named:
        # what names no selection is not forced on the reference
        assert list(checks) == ["selection_valid", "loss_vs_free_reference"]
    else:
        assert checks["selection_valid"][0]
    if "selection_forced" not in named and "selection_valid" not in named:
        assert checks["selection_forced"][0], (defect, checks)


# ---- run.py end to end -----------------------------------------------------

def _selecting(config):
    """``config`` with a selecting attention: the tap's tied indexer in
    its ``sizes``, ``tests/selection_tap.py``'s reference, kind
    ``selected``."""
    from benchmarks.tests import selection_tap

    config = json.loads(json.dumps(config))
    config["sizes"].update(selection_tap.INDEX, indexer_loss_coef=1.0)
    config.update(reference="selection_tap", check={"kind": "selected"})
    return config


def _llama_like():
    like = {
        "n_kv_head": 2, "norm": "rmsnorm", "norm_eps": 1e-6, "act": "swiglu",
        "pos": "rope", "tie_embeddings": False,
    }
    config = json.loads(json.dumps(TINY))
    config["sizes"].update(like)
    config["program"]["overrides"].update(
        {k: v for k, v in like.items() if k != "norm_eps"}
    )
    return config


def _run_selecting(monkeypatch, capsys, config, **kwargs):
    from benchmarks.lib import selected
    from benchmarks.runners import train
    from benchmarks.tests import selection_tap

    selection_tap.install(monkeypatch.setattr)
    monkeypatch.setitem(
        sys.modules, "benchmarks.references.selection_tap",
        selection_tap.reference,
    )
    monkeypatch.setattr(
        selected, "program_logits_and_choices",
        selection_tap.logits_and_choices,
    )
    program_config = train._program_config
    # the indexer's sizes are the tap's, no field of the program's config
    monkeypatch.setattr(train, "_program_config", lambda config: (
        program_config(dict(config, sizes={
            k: v for k, v in config["sizes"].items()
            if not k.startswith("index")
        }))
    ))
    return _run_patched(monkeypatch, capsys, _selecting(config), 0, **kwargs)


# As ``ROUTED_SEED``: a seed whose sound readings sit inside the limits at
# this size. With 512 tokens and 16 keys a query, a moved key is a
# sixteenth of a query's attention and the free-running loss swings
# (1.1e-4..1.6e-3 over six seeds, against 5.3e-6..1.5e-4 at the chip's
# 8192 tokens and 2048 keys, which the limit was set from).
SELECTED_SEED = 3600002001


def test_selected_configuration_is_correct(monkeypatch, capsys):
    rc, _cell, _manifest, lines = _run_selecting(
        monkeypatch, capsys, _llama_like(), seed=SELECTED_SEED
    )
    assert rc == 0
    checks, events = _events(lines)
    assert json.loads(lines[-1])["correct"] is True, lines
    assert list(checks) == [
        "selection_valid", "selection_regret", "selection_moved",
        "logits_vs_reference", "logits_rms_vs_reference",
        "loss_vs_reference", "loss_vs_free_reference", "first_step_loss",
        "no_compile_in_window", "no_failed_step",
    ]
    ref = events["reference"]
    assert ref["kind"] == "selected" and ref["selection_faults"] == 0
    assert len(ref["select_moved_by_layer"]) == TINY["sizes"]["n_layer"]
    assert "regret_max" not in ref and "choice_faults" not in ref


def test_selected_composes_with_routing(monkeypatch, capsys):
    """tiny-moe through the router's tap and the selecting attention
    together: both families of choices are forced, both regrets judged."""
    rc, _cell, _manifest, lines = _run_selecting(
        monkeypatch, capsys, TINY_MOE, seed=ROUTED_SEED
    )
    assert rc == 0
    checks, events = _events(lines)
    assert json.loads(lines[-1])["correct"] is True, lines
    assert list(checks) == [
        "selection_valid", "choices_valid", "selection_regret",
        "selection_moved",
    ] + ROUTED_CHECKS[1:]
    assert all(c["ok"] for c in checks.values()), checks
    ref = events["reference"]
    layers = TINY_MOE["sizes"]["n_layer"]
    assert ref["kind"] == "selected"
    assert len(ref["select_moved_by_layer"]) == layers
    assert len(ref["moved_by_layer"]) == layers
    assert 0 <= ref["regret_max"] <= ref["regret_tol"]
    assert 0 <= ref["select_regret_max"] <= ref["select_regret_tol"]
    assert set(ref["reference_terms"]) == {"moe_lb_loss", "moe_z_loss"}


def _run_block_selecting(monkeypatch, capsys, config, **kwargs):
    """``run.py`` on a program with the block tap's attention: the
    tap's units in ``sizes``, ``block_plain`` as the reference."""
    from benchmarks.lib import selected
    from benchmarks.runners import train
    from benchmarks.tests import block_tap

    block_tap.install(monkeypatch.setattr)
    monkeypatch.setitem(
        sys.modules, "benchmarks.references.block_tap", block_tap.reference
    )
    monkeypatch.setattr(
        selected, "program_logits_and_choices", block_tap.logits_and_choices
    )
    program_config = train._program_config
    # the pooling and the forced units are the tap's, no field of the
    # program's config; ``select_block`` and ``select_groups`` stay: the
    # runner itself passes over them where the program has no such field
    own = {"index_topk", "pool_window", "pool_stride", "select_init_blocks",
           "select_local"}
    monkeypatch.setattr(train, "_program_config", lambda config: (
        program_config(dict(config, sizes={
            k: v for k, v in config["sizes"].items() if k not in own
        }))
    ))
    config = json.loads(json.dumps(config))
    config["sizes"].update(block_tap.UNITS)
    config.update(reference="block_tap", check={"kind": "selected"})
    return _run_patched(monkeypatch, capsys, config, 0, **kwargs)


def test_block_selecting_configuration_is_correct(monkeypatch, capsys):
    """A selection by blocks of 16 keys, one a layer (the program has no
    path by KV head yet), planted from outside: valid in units, no forced
    unit missing, regret and moved at the reference's block scores."""
    rc, _cell, _manifest, lines = _run_block_selecting(
        monkeypatch, capsys, _llama_like(), seed=SELECTED_SEED
    )
    assert rc == 0
    checks, events = _events(lines)
    assert json.loads(lines[-1])["correct"] is True, lines
    assert list(checks) == [
        "selection_valid", "selection_forced", "selection_regret",
        "selection_moved", "logits_vs_reference", "logits_rms_vs_reference",
        "loss_vs_reference", "loss_vs_free_reference", "first_step_loss",
        "no_compile_in_window", "no_failed_step",
    ]
    assert list(json.loads(lines[-1])["checks"]) == list(checks)
    ref = events["reference"]
    assert ref["kind"] == "selected" and ref["selection_faults"] == 0
    assert ref["select_forced_missing"] == 0
    assert ref["select_rows"]["block"] == 16
    assert len(ref["select_moved_by_layer"]) == TINY["sizes"]["n_layer"]
    assert ref["select_gap_median"] > 0  # some query had a choice to make


def test_a_forced_unit_missing_is_not_correct(monkeypatch, capsys):
    """``run.py`` end to end with the initial block left to its score."""
    defects.initial_dropped(monkeypatch.setattr)
    rc, _cell, _manifest, lines = _run_block_selecting(
        monkeypatch, capsys, _llama_like(), seed=SELECTED_SEED
    )
    assert rc == 0
    checks, _events_ = _events(lines)
    assert json.loads(lines[-1])["correct"] is False
    assert checks["selection_valid"]["ok"]
    assert not checks["selection_forced"]["ok"]
    assert checks["selection_forced"]["value"] > 0


# the program's own selecting model at a tiny size (tier-1's
# ``tests/test_keye_reference.py`` runs it so): keys, one selection a layer
TINY_KEYE = dict(
    n_layer=2, d_model=128, n_head=4, n_kv_head=2, d_head=64, vocab_size=512,
    max_seq=128, n_experts=16, expert_top_k=4, d_expert=64, n_experts_held=4,
    expert_offset=4, index_n_heads=4, index_head_dim=16, index_topk=40,
    index_chunk=32, remat="full",
)


def _abstract_keye(batch, seq):
    """The program's own selecting model at a tiny size, its parameters
    as shapes: what it hands over is keys, one selection a layer."""
    import jax
    import jax.numpy as jnp

    from dlrover_tpu.models import decoder, get_config

    cfg = get_config("keye-vl-2.0", **dict(TINY_KEYE, max_seq=seq))
    params = jax.eval_shape(lambda k: decoder.init(k, cfg), jax.random.key(0))
    return cfg, params, jax.ShapeDtypeStruct((batch, seq), jnp.int32)


@pytest.mark.parametrize("sizes,shape", [
    ({"select_block": 16}, "bool [selecting layers x 1, 2, 128, 8]"),
    ({"select_groups": 2, "select_block": 64},
     "bool [selecting layers x 2, 2, 128, 2]"),
    ({"select_block": 48}, "128 tokens, is no whole number of them"),
    # the program's two layers are no whole number of three selections
    ({"select_groups": 3}, "bool [selecting layers x 3, 2, 128, 128]"),
])
def test_a_selection_in_other_units_is_refused_by_its_shape(sizes, shape):
    """The program as it is hands KEYS over, one selection a layer. A
    configuration that states blocks or groups is refused before
    anything compiles, with the shape it should have had beside the one
    it has."""
    from benchmarks.lib import selected
    from benchmarks.lib.device import Refused

    cfg, params, tokens = _abstract_keye(2, 128)
    with pytest.raises(Refused) as refused:
        selected.program_logits_and_choices(
            params, tokens, cfg, dict(sizes, index_topk=40)
        )
    text = str(refused.value)
    assert shape in text and "attn_selected" in text
    if "no whole number" not in shape:
        assert "hands over bool [2, 2, 128, 128]" in text


def test_run_refuses_keys_where_blocks_are_stated(monkeypatch, capsys):
    """``run.py`` end to end on the program as it is (its own selecting
    model, tiny) under a configuration whose ``sizes`` name
    ``select_block``: exit code 2, no result line."""
    from dlrover_tpu.models import get_config

    over = dict(TINY_KEYE)
    cfg = get_config("keye-vl-2.0", **over)
    with open(os.path.join(
        ROOT, "benchmarks", "configs", "keye-vl-2.0-ep8-1chip.json"
    )) as f:
        keys = json.load(f)["sizes"]
    config = {
        "source": "test",
        "program": dict(TINY["program"], model="keye-vl-2.0", overrides=over),
        "sizes": dict(
            {k: getattr(cfg, k) for k in keys if k != "norm_eps"},
            norm_eps=1e-6, select_block=16,
        ),
        "reference": "keye_vl2_plain", "check": {"kind": "selected"},
    }
    from benchmarks.lib import selected

    rc, _cell, _manifest, lines = _run_patched(monkeypatch, capsys, config, 0)
    assert rc == 2
    assert lines[-1].startswith("refused:") and "select_block 16" in lines[-1]
    assert "bool [selecting layers x 1, 4, 128, 8]" in lines[-1]
    assert "hands over bool [2, 4, 128, 128]" in lines[-1]
    assert not any(line.startswith("{") for line in lines)
    # and the same configuration without the key is what the program runs
    assert selected.units(dict(config["sizes"], select_block=1)) == (40, 1, 1)


def test_program_without_a_selection_is_refused(monkeypatch, capsys):
    """The program as it is hands no ``attn_selected`` over: refused
    before any check program compiles, exit code 2."""
    config = dict(_llama_like(), check={"kind": "selected"})
    rc, _cell, _manifest, lines = _run_patched(monkeypatch, capsys, config, 0)
    assert rc == 2
    assert lines[-1].startswith("refused:") and "attn_selected" in lines[-1]
    assert not any(line.startswith("{") for line in lines)


def test_unknown_kind_still_raises(monkeypatch, capsys):
    config = dict(_llama_like(), check={"kind": "windowed"})
    with pytest.raises(ValueError, match="no comparison of kind 'windowed'"):
        _run_patched(monkeypatch, capsys, config, 0)
