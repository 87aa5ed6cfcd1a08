"""Fused multi-step train blocks: K steps per device dispatch.

Pins the two contracts the fused engine must keep:

1. NUMERICS — ``train_block(K)`` is bitwise-identical to K sequential
   ``step_fn`` calls (same params, opt state, per-step metrics), so
   turning the knob can never change training.
2. CADENCE — saves/evals/logs/max_steps land on the SAME global steps
   as the unfused loop for any K (blocks auto-shrink onto boundaries),
   control flags raised mid-block are honored at the next boundary,
   and no step is lost or double-counted.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax

from dlrover_tpu.models.config import get_config
from dlrover_tpu.observability.loss_spike import LossSpikeDetector
from dlrover_tpu.observability.profiler import StepClock
from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh
from dlrover_tpu.train.callbacks import Callback
from dlrover_tpu.train.optimizer import make_optimizer
from dlrover_tpu.train.train_step import TrainStepBuilder, init_train_state
from dlrover_tpu.train.trainer import Trainer, TrainerArgs


def _cfg():
    return get_config(
        "tiny", n_layer=2, d_model=64, d_ff=128, n_head=4,
        vocab_size=128, max_seq=32,
    )


def _data_iter(batch=8, seq=32, seed=0, limit=None):
    rng = np.random.RandomState(seed)
    n = 0
    while limit is None or n < limit:
        base = rng.randint(0, 8, size=(batch, seq + 1))
        yield {
            "tokens": jnp.asarray(base[:, :-1], jnp.int32),
            "targets": jnp.asarray(base[:, 1:], jnp.int32),
        }
        n += 1


# ---------------------------------------------------------------------------
# numerics: the block IS K steps
# ---------------------------------------------------------------------------


def test_train_block_bitwise_equals_sequential_steps():
    cfg = _cfg()
    mesh = build_mesh(MeshConfig(dp=-1))
    opt = optax.adamw(1e-3)
    builder = TrainStepBuilder(cfg, mesh, opt)
    K = 4
    it = _data_iter(seed=3)
    batches = [next(it) for _ in range(K)]

    step = jax.jit(builder.step_fn)
    state_seq = init_train_state(jax.random.key(0), cfg, mesh, opt)
    seq_losses, seq_gnorms = [], []
    for b in batches:
        state_seq, m = step(state_seq, b)
        seq_losses.append(float(m["loss"]))
        seq_gnorms.append(float(m["grad_norm"]))

    state_blk = init_train_state(jax.random.key(0), cfg, mesh, opt)
    block = jax.tree.map(lambda *xs: jnp.stack(xs), *batches)
    state_blk, metrics = builder.build_block()(state_blk, block)

    # state: bitwise over every leaf (params, both Adam moments, step)
    for a, b in zip(jax.tree.leaves(state_seq), jax.tree.leaves(state_blk)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    # metrics stack per step, in order, bitwise
    assert metrics["loss"].shape == (K,)
    assert np.array_equal(
        np.asarray(metrics["loss"], np.float32),
        np.asarray(seq_losses, np.float32),
    )
    assert np.array_equal(
        np.asarray(metrics["grad_norm"], np.float32),
        np.asarray(seq_gnorms, np.float32),
    )


def test_block_builder_rejects_offloaded_opt_state():
    cfg = _cfg()
    mesh = build_mesh(MeshConfig(dp=-1))
    builder = TrainStepBuilder(
        cfg, mesh, optax.adamw(1e-3), offload_opt_state=True
    )
    with pytest.raises(NotImplementedError):
        builder.build_block()


# ---------------------------------------------------------------------------
# cadence: fused loop == unfused loop, for awkward K
# ---------------------------------------------------------------------------


class _Recorder(Callback):
    """Record every step/save/eval/log the loop emits, in order."""

    def __init__(self):
        self.steps = []
        self.losses = {}
        self.saves = []
        self.evals = []
        self.logs = []

    def on_step_end(self, trainer, step, metrics, control):
        self.steps.append(step)
        self.losses[step] = metrics["loss"]

    def on_save(self, trainer, step, control):
        self.saves.append(step)

    def on_eval(self, trainer, step, metrics, control):
        self.evals.append(step)

    def on_log(self, trainer, step, logs, control):
        self.logs.append(step)


def _run(tmp_path, block_k, max_steps=13, save_interval=6,
         eval_interval=0, callbacks=None, limit=None, tag=""):
    cfg = _cfg()
    mesh = build_mesh(MeshConfig(dp=2, fsdp=4))
    rec = _Recorder()
    args = TrainerArgs(
        output_dir=str(tmp_path / f"k{block_k}{tag}"),
        max_steps=max_steps,
        log_interval=4,
        save_interval=save_interval,
        eval_interval=eval_interval,
        report_to_master=False,
        block_k=block_k,
    )
    trainer = Trainer(
        cfg, args, _data_iter(limit=limit),
        make_optimizer(learning_rate=3e-3, warmup_steps=2, decay_steps=100),
        mesh=mesh,
        eval_iter_fn=(lambda: _data_iter(seed=9)) if eval_interval else None,
        callbacks=[rec] + list(callbacks or []),
    )
    state = trainer.train()
    return trainer, rec, state


# tier-1 budget: block_k=3 exercises the auto-shrink boundary logic on
# the fast tier; the other widths re-prove the same property and ride
# the slow tier
@pytest.mark.parametrize(
    "block_k",
    [3] + [pytest.param(k, marks=pytest.mark.slow) for k in (5, 8, 13, 64)],
)
def test_blockwise_cadences_match_stepwise(tmp_path, block_k):
    # 13 steps, save every 6, log every 4: none of these divide the
    # block sizes, so every boundary requires the auto-shrink
    _, base, state1 = _run(tmp_path, 1, tag="base%d" % block_k)
    _, fused, statek = _run(tmp_path, block_k)

    assert base.steps == list(range(1, 14))
    assert fused.steps == base.steps  # no lost or double-counted steps
    assert fused.saves == base.saves == [6, 12]
    assert fused.logs == base.logs == [4, 8, 12]
    assert int(state1["step"]) == int(statek["step"]) == 13
    # identical batches + bitwise-equivalent engine ⇒ identical losses
    for s in base.steps:
        assert fused.losses[s] == base.losses[s]


@pytest.mark.slow  # tier-1 budget: trainer covers these cadence/exhaustion paths fast
def test_blockwise_eval_cadence_and_final_partial_block(tmp_path):
    _, rec, state = _run(
        tmp_path, 4, max_steps=10, save_interval=0, eval_interval=5,
    )
    assert rec.steps == list(range(1, 11))
    assert rec.evals == [5, 10]  # block shrank 4→1 to land on step 5
    assert int(state["step"]) == 10


@pytest.mark.slow  # tier-1 budget: trainer covers these cadence/exhaustion paths fast
def test_blockwise_data_exhaustion_runs_partial_block(tmp_path):
    # 10 batches with block_k=4: final block is a partial (2-step) one;
    # every consumed batch must become exactly one step
    _, rec, state = _run(
        tmp_path, 4, max_steps=100, save_interval=0, limit=10,
    )
    assert rec.steps == list(range(1, 11))
    assert int(state["step"]) == 10


class _FlagAt(Callback):
    """Raise a control flag from inside the drain, mid-block."""

    def __init__(self, step, flag):
        self._step = step
        self._flag = flag

    def on_step_end(self, trainer, step, metrics, control):
        if step == self._step:
            setattr(control, self._flag, True)


@pytest.mark.slow
def test_mid_block_save_flag_honored_at_next_boundary(tmp_path):
    # drain of block [1..5] sees step 3 raise should_save while block
    # [6..10] is in flight: the save must land at a block end (10 or
    # 15), at most ONE block after the flag, with no mid-block save
    trainer, rec, _ = _run(
        tmp_path, 5, max_steps=20, save_interval=0,
        callbacks=[_FlagAt(3, "should_save")],
    )
    assert len(rec.saves) >= 1
    assert rec.saves[0] in (10, 15)  # next boundary after the drain
    assert rec.saves[0] % 5 == 0
    # the save is real: that step's checkpoint committed
    assert trainer.checkpointer.latest_committed_step() >= rec.saves[0]


@pytest.mark.slow
def test_mid_block_stop_flag_stops_at_boundary(tmp_path):
    _, rec, state = _run(
        tmp_path, 5, max_steps=100, save_interval=0,
        callbacks=[_FlagAt(2, "should_stop")],
    )
    final = int(state["step"])
    # stopped at a block boundary, within one block of the flag
    assert final % 5 == 0 and final <= 15
    assert rec.steps == list(range(1, final + 1))


def test_next_block_k_never_overshoots_boundaries(tmp_path):
    cfg = _cfg()
    mesh = build_mesh(MeshConfig(dp=-1))
    args = TrainerArgs(
        output_dir=str(tmp_path), max_steps=97, save_interval=7,
        eval_interval=5, memory_save_interval=3, block_k=8,
        report_to_master=False,
    )
    trainer = Trainer(
        cfg, args, _data_iter(),
        make_optimizer(learning_rate=1e-3, warmup_steps=2, decay_steps=10),
        mesh=mesh,
    )
    for step in range(0, 97):
        k = trainer._next_block_k(step)
        assert 1 <= k <= 8
        end = step + k
        assert end <= 97
        for boundary in (7, 5, 3):
            # no cadence boundary strictly inside (step, end)
            for s in range(step + 1, end):
                assert s % boundary != 0, (step, k, boundary)


# ---------------------------------------------------------------------------
# stacked-metrics ingestion (loss spikes at the exact step; timer)
# ---------------------------------------------------------------------------


def test_loss_spike_update_block_fires_at_exact_step(tmp_path):
    det = LossSpikeDetector(
        save_dir=str(tmp_path), min_iter=0, min_loss=1.0, zscore=None
    )
    # warm block, then a block whose 3rd step spikes
    assert det.update_block(0, np.asarray([0.5, 0.6, 0.5, 0.4])) == []
    spiked = det.update_block(4, np.asarray([0.5, 0.4, 7.5, 0.5]))
    assert spiked == [6]
    assert det.spikes == [(6, 7.5)]
    # jax arrays (what a drained metrics block actually holds) work too
    spiked = det.update_block(8, jnp.asarray([9.0, 0.3]))
    assert spiked == [8]


def test_step_timer_attributes_block_time_per_step():
    t = StepClock(beat=False)
    t.record(0.8, n_steps=8)
    assert t.steps == 8
    assert t.last_s == pytest.approx(0.1)
    t.record(0.1)  # unfused records still work alongside
    assert t.steps == 9
    assert t.last_s == pytest.approx(0.1)
