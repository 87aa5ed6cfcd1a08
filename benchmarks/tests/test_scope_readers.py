"""The readers that PR 33's hand-overs feed, on a hand-built ``run``:
``moe.sort_combine_share`` sums the first device's rows under its two
scopes, says what it summed, and fails where a traced step has no row
under them; a reader that ignores ``op_names`` reads what it read; ``train_step.mfu`` divides the count
the runner resolved."""

import pytest

from benchmarks.lib import peaks
from benchmarks.tests.test_zero_readers import _reader

STEP = "jit(step_fn)/transpose(jvp())/while/body/closed_call/checkpoint/"
BY_NAME = {
    "fusion.400 fusion bf16[65536,2048]": [0.006, 9],
    "fusion.353 fusion bf16[8192,8,2048]": [0.003, 9],
    "fusion.12 fusion f32[8192,64]": [0.001, 9],
    "ragged-dot-none.7 custom-call tpu_custom_call bf16[65536,1024]": [0.03, 36],
    "fusion.269 fusion bf16[2,4096,2048]": [0.06, 9],
}
OP_NAMES = {
    "fusion.400 fusion bf16[65536,2048]":
        STEP + "rematted_computation/mlp/moe.sort/gather",
    "fusion.353 fusion bf16[8192,8,2048]": STEP + "mlp/moe.combine/gather",
    "fusion.12 fusion f32[8192,64]": STEP + "mlp/moe.route/dot_general",
    "ragged-dot-none.7 custom-call tpu_custom_call bf16[65536,1024]":
        "ragged-dot-none",
    "fusion.269 fusion bf16[2,4096,2048]": STEP + "attn/dot_general",
}


def _run(op_names, said=None):
    first = {
        "busy_s": 0.1, "by_name": BY_NAME, "modules": ["jit_step_fn"],
        "op_names": {k: {v: BY_NAME[k][0]} for k, v in op_names.items()},
    }
    other = {"busy_s": 0.1, "by_name": {}, "op_names": {}}
    said = [] if said is None else said
    return {
        "trace": {"per_device": [first, other]},
        "say": lambda **record: said.append(record),
    }


def test_sort_combine_share_sums_its_scopes():
    read = _reader("moe.sort_combine_share")
    # 6 ms of sort and 3 ms of combine in 100 ms busy; the router, the
    # grouped matmuls and attention are not in it
    said = []
    assert read(_run(OP_NAMES, said)) == pytest.approx(9.0)
    assert said == [{
        "event": "scope_rows", "metric": "moe.sort_combine_share",
        "busy_s": 0.1, "modules": ["jit_step_fn"],
        "rows": {"moe.sort": [1, pytest.approx(0.006)],
                 "moe.combine": [1, pytest.approx(0.003)]},
    }]
    # a row whose merged path names both scopes counts once
    both = dict(OP_NAMES)
    both["fusion.12 fusion f32[8192,64]"] = (
        STEP + "mlp/moe.sort/iota;" + STEP + "mlp/moe.combine/mul"
    )
    assert read(_run(both)) == pytest.approx(10.0)
    # no profile: nothing to read
    assert read({"trace": None}) is None


@pytest.mark.parametrize("gone", ["everything", "moe.sort", "moe.combine"])
def test_sort_combine_share_fails_where_a_scope_is_gone(gone):
    """No compiled text reached the reduction, or the program lost a
    scope: the cells this reader is listed for fail, they do not drop
    the metric (and never read 0)."""
    read = _reader("moe.sort_combine_share")
    left = {} if gone == "everything" else {
        k: v for k, v in OP_NAMES.items() if gone not in v
    }
    said = []
    with pytest.raises(LookupError, match="moe.sort"):
        read(_run(left, said))
    assert said[0]["rows"][gone if gone != "everything" else "moe.sort"][0] == 0


def test_a_reader_that_ignores_op_names_reads_what_it_read():
    read = _reader("moe.grouped_matmul_share")
    assert read(_run(OP_NAMES)) == read(_run({})) == pytest.approx(30.0)


def test_mfu_divides_the_resolved_count():
    run = {
        "window": {"steps": 10, "tokens": 8192, "seconds": 4.0},
        "required_flops_per_token": 5e9, "chips": 1,
        "peaks": peaks.chip_peaks("TPU v5 lite"),
    }
    assert _reader("train_step.mfu")(run) == pytest.approx(
        100.0 * 20480 * 5e9 / 197e12
    )
    assert _reader("train_step.mfu")(
        dict(run, window=dict(run["window"], steps=0))
    ) is None
