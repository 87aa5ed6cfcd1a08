"""What PR 53 adds to the benchmark, on the CPU: the required FLOPs of
``references/jamba_plain.py`` by hand, the committed file's ``sizes``
against the program's model with its overrides and against the
catalog's published keys, the three new readers on a recorded
``op_names`` table, ``run.py`` end to end at a tiny size of this
architecture, and the defects of ``jamba_defects.py``, each of which
the ``dense`` comparison has to fail."""

import json
import os

import pytest

from benchmarks.lib import flops
from benchmarks.references import jamba_plain as plain
from benchmarks.tests import jamba_defects as defects
from benchmarks.tests import test_rehearsal as rehearsal
from benchmarks.tests.test_zero_readers import _reader

ROOT = rehearsal.ROOT
CELL = "jamba2-3b-l14-train-b1s8192"
CONFIG = "jamba2-3b-l14"
PERIOD = "m-" * 7 + "*-" + "m-" * 6


def _config():
    path = os.path.join(ROOT, "benchmarks", "configs", CONFIG + ".json")
    with open(path) as f:
        return json.load(f)


# ---- required FLOPs ---------------------------------------------------------
# per layer, by hand (the published widths): a Mamba-1 mixer 2560 x 10,240
# + 5120 x 192 + 160 x 5120 + 5120 x 2560 = 41,123,840 in its four matrices
# and 2 x 5120 x 16 = 163,840 multiply-adds of the recurrence; the
# attention 2 x 2560^2 + 2 x 2560 x 128 = 13,762,560; an MLP 3 x 2560 x
# 8192 = 62,914,560; the tied head 2560 x 65,536 once.


def test_required_terms_by_hand():
    sizes = _config()["sizes"]
    terms = plain.required_terms(sizes, 8192)
    mamba = 41_123_840 + 163_840
    by_hand = 13 * mamba + 13_762_560 + 14 * 62_914_560 + 167_772_160
    assert terms["multiplied_params"] == by_hand == 1_599_078_400
    # one attention layer of 20 x 128
    assert terms["attention_pair_channels"] == 2560 * 4096.5
    need = flops.resolve(_config(), 8192)
    assert need == 6.0 * by_hand + 12.0 * 2560 * 4096.5
    assert round(need / 1e9, 2) == 9.72
    # the shares ISSUE 53 states: mixers 33.1%, MLPs 54.4%, head 10.4%,
    # the attention layer 2.1% (pairs 1.3%), the recurrence 0.13%
    assert round(13 * 6 * mamba / need, 3) == 0.331
    assert round(14 * 6 * 62_914_560 / need, 3) == 0.544
    assert round(6 * 167_772_160 / need, 3) == 0.104
    assert round(
        (6 * 13_762_560 + 12 * 2560 * 4096.5) / need, 3
    ) == 0.021
    assert round(12 * 2560 * 4096.5 / need, 3) == 0.013
    assert round(13 * 6 * 163_840 / need, 4) == 0.0013


def test_required_terms_follow_the_layers():
    sizes = _config()["sizes"]
    base = plain.required_terms(sizes, 8192)
    longer = plain.required_terms(
        dict(sizes, n_layer=15, layer_pattern=PERIOD + "m-"), 8192
    )
    assert longer["multiplied_params"] - base["multiplied_params"] == (
        41_123_840 + 163_840 + 62_914_560
    )
    twice = plain.required_terms(
        dict(sizes, n_layer=28, layer_pattern=PERIOD * 2), 8192
    )
    assert twice["attention_pair_channels"] == 2 * 2560 * 4096.5
    with pytest.raises(ValueError, match="a mixer"):
        plain.required_terms(dict(sizes, layer_pattern=PERIOD[:-1]), 8192)
    with pytest.raises(ValueError, match="a mixer"):
        plain.required_terms(
            dict(sizes, layer_pattern="M-" + PERIOD[2:]), 8192
        )


# ---- the file against the program and the source ----------------------------


def test_sizes_are_the_programs_model_with_its_overrides():
    from benchmarks.runners.train import _program_config

    config = _config()
    cfg = _program_config(config)  # raises on a size the program lacks
    assert cfg.layer_pattern == PERIOD and cfg.n_layer == 14
    assert cfg.train_only.startswith("state-space layers")
    assert cfg.num_params() == 1_598_556_096
    assert cfg.flops_per_token(8192) == flops.resolve(config, 8192)
    # the full model's published layers hold this period first
    from dlrover_tpu.models import get_config

    full = get_config(config["program"]["model"])
    assert full.layer_pattern == PERIOD * 2
    assert (full.n_layer, full.vocab_size, full.max_seq) == (
        28, 65536, 262144
    )


def test_file_holds_the_published_keys_but_for_the_stated_cuts():
    config = _config()
    manifest = rehearsal._manifest()
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert sorted(entry["reduced"]) == sorted(config["reduced"]) == [
        "max_position_embeddings", "num_hidden_layers",
    ]
    assert entry["source"] == config["source"] == (
        "https://huggingface.co/ai21labs/AI21-Jamba2-3B/blob/main/config.json"
    )
    published = {
        "attn_layer_offset": 7, "attn_layer_period": 14,
        "expert_layer_offset": 1, "expert_layer_period": 2,
        "hidden_act": "silu", "hidden_size": 2560,
        "intermediate_size": 8192, "mamba_conv_bias": True,
        "mamba_d_conv": 4, "mamba_d_state": 16, "mamba_dt_rank": 160,
        "mamba_expand": 2, "mamba_proj_bias": False, "model_type": "jamba",
        "num_attention_heads": 20, "num_experts": 1,
        "num_experts_per_tok": 1, "num_key_value_heads": 1,
        "num_logits_to_keep": 1, "rms_norm_eps": 1e-06,
        "sliding_window": None, "tie_word_embeddings": True,
        "use_mamba_kernels": True, "vocab_size": 65536,
    }
    assert {k: config[k] for k in published} == published
    cut = {"num_hidden_layers": 14, "max_position_embeddings": 8192}
    assert {k: config[k] for k in cut} == cut
    for key in ("deployment", "parameters", "assumed"):
        assert config[key]
    assert "1,598,556,096" in config["parameters"]["total"]
    sizes = config["sizes"]
    assert (sizes["d_model"], sizes["d_ff"], sizes["vocab_size"]) == (
        config["hidden_size"], config["intermediate_size"],
        config["vocab_size"],
    )
    assert (
        sizes["mamba_expand"], sizes["mamba_dt_rank"],
        sizes["ssm_state_size"], sizes["conv_kernel"],
    ) == (2, 160, 16, 4)
    assert sizes["norm_eps"] == config["rms_norm_eps"]
    assert config["check"] == {"kind": "dense"}
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "b1s8192", 1
    )


# ---- the scope readers ------------------------------------------------------
# rows as a traced step of the cell names them (op_names of the compiled
# text: forward, the part's recomputation and the backward; the scan's
# loops inside a scanned run of layers)

STEP = "jit(step_fn)/jit(main)/"
FWD = STEP + "jvp(while)/body/ssm1/"
BACK = STEP + "transpose(jvp(while))/body/transpose(jvp(ssm1))/"
BY_NAME = {
    "fusion.11 fusion bf16[1,8192,10240]": [0.10, 39],
    "fusion.12 fusion f32[1,8192,5120]": [0.05, 39],
    "fusion.13 fusion bf16[1,8192,192]": [0.02, 39],
    "fusion.14 fusion f32[1,8192,5120]": [0.03, 39],
    "fusion.15 fusion f32[1,16,5120]": [0.50, 26],
    "fusion.16 fusion f32[256,1,16,5120]": [0.40, 13],
    "fusion.17 fusion bf16[1,8192,5120]": [0.04, 13],
    "fusion.21 fusion bf16[1,8192,8192]": [0.30, 42],
    "fusion.22 fusion bf16[1,8192,2560]": [0.06, 3],
}
OP_NAMES = {
    "fusion.11 fusion bf16[1,8192,10240]": FWD + "checkpoint/dot_general",
    "fusion.12 fusion f32[1,8192,5120]":
        FWD + "checkpoint/ssm1.conv/ssm.conv/add",
    "fusion.13 fusion bf16[1,8192,192]":
        FWD + "checkpoint/ssm1.dbc/dot_general",
    "fusion.14 fusion f32[1,8192,5120]":
        BACK + "checkpoint/ssm1.dbc/logistic",
    "fusion.15 fusion f32[1,16,5120]":
        FWD + "checkpoint/ssm1.scan/while/body/while/body/exp",
    "fusion.16 fusion f32[256,1,16,5120]":
        BACK + "checkpoint/ssm1.scan/while/body/while/body/mul",
    "fusion.17 fusion bf16[1,8192,5120]": BACK + "checkpoint/mul",
    "fusion.21 fusion bf16[1,8192,8192]":
        STEP + "jvp(while)/body/mlp/checkpoint/dot_general",
    "fusion.22 fusion bf16[1,8192,2560]":
        STEP + "jvp(attn)/checkpoint/dot_general",
}


def _run(op_names=OP_NAMES, said=None):
    first = {
        "busy_s": 2.0, "by_name": BY_NAME, "modules": ["jit_step_fn"],
        "op_names": {k: {v: BY_NAME[k][0]} for k, v in op_names.items()},
    }
    said = [] if said is None else said
    return {
        "trace": {"per_device": [first]},
        "say": lambda **record: said.append(record),
    }


@pytest.mark.parametrize(
    "metric,scope,rows,seconds",
    [
        ("ssm1.mixer_share", "ssm1", 7, 1.14),
        ("ssm1.scan_share", "ssm1.scan", 2, 0.90),
        ("ssm1.dbc_share", "ssm1.dbc", 2, 0.05),
    ],
)
def test_scope_share_readers(metric, scope, rows, seconds):
    read, said = _reader(metric), []
    assert read(_run(said=said)) == pytest.approx(100.0 * seconds / 2.0)
    assert said == [{
        "event": "scope_rows", "metric": metric, "busy_s": 2.0,
        "modules": ["jit_step_fn"],
        "rows": {scope: [rows, pytest.approx(seconds)]},
    }]
    assert read({"trace": None}) is None
    # the scope gone from a traced step: an error, not a metric left out
    gone = {
        k: v for k, v in OP_NAMES.items()
        if scope not in v.replace("(", "/").replace(")", "/").split("/")
    }
    with pytest.raises(LookupError, match=scope):
        read(_run(op_names=gone))


def test_new_metrics_are_listed_for_this_cell_alone():
    manifest = rehearsal._manifest()
    for name in ("ssm1.mixer_share", "ssm1.scan_share", "ssm1.dbc_share"):
        entry = next(m for m in manifest["per_layer"] if m["name"] == name)
        assert entry["workloads"] == [CELL]
        assert entry["moves"] == "train_tokens_per_s"
        assert entry["source"] == "device_trace" and entry["unit"] == "%"
        assert entry["layer"] == "selective-scan layer"
    # what was there keeps its lists
    for name in ("ssm.mixer_share", "ssm.scan_share", "mtp.share"):
        entry = next(m for m in manifest["per_layer"] if m["name"] == name)
        assert CELL not in entry["workloads"]


# ---- run.py end to end at a tiny size ---------------------------------------

_OVERRIDES = {
    "n_layer": 5, "layer_pattern": "m-m-*-m-m-", "d_model": 128, "n_head": 4,
    "n_kv_head": 1, "d_head": 32, "d_ff": 256, "vocab_size": 512,
    "max_seq": 128, "mamba_dt_rank": 8, "ssm_state_size": 8,
    "remat": "full", "attn_block_q": 128, "attn_block_k": 128,
}
TINY = {
    "source": "test",
    "program": {
        "model": "jamba2-3b",
        # float32 on both sides, so that a defect shows by orders of
        # magnitude; the chip's recipe is bf16
        "overrides": dict(_OVERRIDES, dtype="float32"),
        "mesh": {"dp": -1},
        "comm": None,
        "optimizer": {"learning_rate": 1e-4, "warmup_steps": 2,
                      "decay_steps": 100},
    },
    "sizes": dict(
        {k: v for k, v in _OVERRIDES.items()
         if k not in ("attn_block_q", "attn_block_k")},
        norm="rmsnorm", norm_eps=1e-6, act="swiglu", pos="none",
        tie_embeddings=True, mamba_expand=2, conv_kernel=4,
    ),
    "reference": "jamba_plain",
    "check": {"kind": "dense"},
}


LONG = 2048  # tokens of the one defect that needs a long memory to show


def _this_cell_first(monkeypatch):
    """The rehearsal runs ``manifest["workloads"][0]``: here, this cell."""
    manifest = rehearsal._manifest()
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    monkeypatch.setattr(
        rehearsal, "_manifest", lambda: dict(manifest, workloads=[cell])
    )
    return manifest


@pytest.mark.parametrize("trace", [0, 1])
def test_tiny_cell_end_to_end(monkeypatch, capsys, trace):
    manifest = _this_cell_first(monkeypatch)
    rc, cell, _, lines = rehearsal._run_patched(
        monkeypatch, capsys, TINY, trace
    )
    assert rc == 0 and cell["name"] == CELL
    result = json.loads(lines[-1])
    checks, events = rehearsal._events(lines)
    assert list(checks) == rehearsal.DENSE_CHECKS
    assert all(c["ok"] for c in checks.values()), checks
    assert result["correct"] is True and result["failed"] == 0
    assert events["reference"]["logit_err"] < 1e-4
    if not trace:
        assert set(result["metrics"]) == {"train_tokens_per_s", "setup_s"}
        return
    listed = {
        m["name"] for m in manifest["per_layer"]
        if "workloads" not in m or CELL in m["workloads"]
    }
    assert {
        "ssm1.mixer_share", "ssm1.scan_share", "ssm1.dbc_share"
    } <= listed
    # no device plane on the CPU: the trace readers return nothing and
    # the line leaves them out
    assert set(result["metrics"]) <= listed
    assert "ssm1.scan_share" not in result["metrics"]


@pytest.mark.parametrize("defect", sorted(defects.INJECT))
def test_comparison_fails(monkeypatch, capsys, defect):
    """Sound, the tiny cell reads 1e-6 on the logits; each defect has to
    push a check past the CHIP's limits (4e-2 at the maximum, 2.5e-2
    rms, 2e-4 on the loss), which are the ones ``run.py`` holds."""
    _this_cell_first(monkeypatch)
    config = TINY
    if defect == "bf16_decays":
        # a decay of 0.999 rounds to 1: it shows once a state lives a
        # thousand tokens, as every state of the chip's 8,192 does
        monkeypatch.setattr(rehearsal, "TINY_TRAFFIC", dict(
            rehearsal.TINY_TRAFFIC, global_batch=1, seq=LONG
        ))
        config = dict(TINY, **{
            key: dict(TINY[key], max_seq=LONG) for key in ("sizes",)
        })
        config["program"] = dict(
            TINY["program"],
            overrides=dict(TINY["program"]["overrides"], max_seq=LONG),
        )
    defects.INJECT[defect](monkeypatch.setattr)
    rc, _, _, lines = rehearsal._run_patched(monkeypatch, capsys, config, 0)
    assert rc == 0
    checks, _ = rehearsal._events(lines)
    failed = {name for name, c in checks.items() if not c["ok"]}
    assert failed & set(defects.CAUGHT_BY[defect]), (defect, checks)
    assert json.loads(lines[-1])["correct"] is False
