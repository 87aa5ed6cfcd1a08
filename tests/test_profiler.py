"""Observability tier: loss-spike, numerics (the step clock:
``tests/test_step_clock.py``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.observability import (
    LossSpikeDetector,
    NumericChecker,
    check_finite,
    sanitize_grads,
)


def test_loss_spike_detector(tmp_path):
    det = LossSpikeDetector(
        save_dir=str(tmp_path), min_iter=10, min_loss=3.0, zscore=4.0,
        window=50,
    )
    # warmup: high loss before min_iter is not a spike
    assert not det.update(1, 9.0)
    for it in range(10, 60):
        assert not det.update(it, 2.0 + 0.01 * np.random.rand())
    # spike above floor + z-score, with per-sample culprits
    assert det.update(
        60, 7.5, sample_ids=[11, 22, 33, 44],
        per_sample_losses=[1.0, 9.0, 2.0, 8.0],
    )
    # another z-score spike just above the floor (spike at 60 must not
    # have poisoned the rolling baseline)
    assert det.update(61, 4.0)
    # below the absolute floor is never a spike, however anomalous
    assert not det.update(62, 2.9)

    # a plateau above the floor does not flag every step: z-score gate
    det2 = LossSpikeDetector(
        save_dir=None, min_iter=0, min_loss=3.0, zscore=4.0, window=50
    )
    flagged = sum(det2.update(i, 4.5 + 0.01 * (i % 3)) for i in range(100))
    assert flagged == 0
    files = list(tmp_path.iterdir())
    assert files
    records = LossSpikeDetector.decode(str(files[0]))
    assert records[0][1] == 60 and records[0][2] == 7.5
    # worst sample (id 22, loss 9.0) listed first
    assert records[0][3].startswith("22:9.0")


def test_numeric_checker_and_finite():
    a = {"w": jnp.ones((4, 4)), "b": jnp.zeros(4)}
    b = {"w": jnp.ones((4, 4)) * (1 + 1e-6), "b": jnp.zeros(4)}
    chk = NumericChecker(rtol=1e-3)
    assert chk.allclose(a, b)
    b["w"] = b["w"].at[0, 0].set(2.0)
    assert not chk.allclose(a, b)
    rep = chk.compare(a, b)
    assert any(r.get("max_abs_err", 0) > 0.5 for r in rep.values())

    bad = {"w": jnp.array([1.0, jnp.nan]), "b": jnp.zeros(2)}
    names = check_finite(bad)
    assert len(names) == 1 and "w" in names[0]


@pytest.mark.parametrize("mode", ["skip", "zero"])
def test_sanitize_grads(mode):
    tx = sanitize_grads(mode)
    params = {"w": jnp.ones(3)}
    state = tx.init(params)
    good = {"w": jnp.array([1.0, 2.0, 3.0])}
    upd, state = jax.jit(tx.update)(good, state)
    assert jnp.allclose(upd["w"], good["w"])
    assert int(state.nonfinite_count) == 0

    bad = {"w": jnp.array([1.0, jnp.inf, 3.0])}
    upd, state = jax.jit(tx.update)(bad, state)
    assert int(state.nonfinite_count) == 1
    if mode == "skip":
        assert jnp.allclose(upd["w"], 0.0)
    else:
        assert jnp.allclose(upd["w"], jnp.array([1.0, 0.0, 3.0]))
