"""What PR 63 adds to the benchmark, on the CPU: the required FLOPs of
``references/qwen3_next_plain.py`` by hand, the committed file's
``sizes`` against the program's model with its overrides and against the
catalog's published keys, the delta rule's operations and bytes by hand,
the six new readers on a recorded ``op_names`` table (and on a program
without the scopes, where they read nothing), ``run.py`` end to end at a
tiny size of this architecture, and the defects of
``qwen3next_defects.py``, each of which the comparison has to fail."""

import json
import os

import pytest

from benchmarks.lib import flops, gdn, peaks
from benchmarks.lib.spans import Spans
from benchmarks.references import qwen3_next_plain as plain
from benchmarks.tests import qwen3next_defects as defects
from benchmarks.tests import test_rehearsal as rehearsal
from benchmarks.tests.test_zero_readers import _reader

ROOT = rehearsal.ROOT
CELL = "qwen3next-ep16-train-b1s16384"
CONFIG = "qwen3-next-80b-a3b-ep16-1chip"
METRICS = (
    "gdn.mixer_share", "gdn.rule_share", "gdn.rule_roofline",
    "gdn.attn_share", "gdn.moe_share", "gdn.held_rows_ratio",
)


def _config():
    path = os.path.join(ROOT, "benchmarks", "configs", CONFIG + ".json")
    with open(path) as f:
        return json.load(f)


# ---- required FLOPs ---------------------------------------------------------
# per part, by hand (the published widths): a mixer 2048 x 12,288 + 2048 x
# 64 + 4096 x 2048 = 33,685,504 in its three matrices and 3.5 x 32 x 128 x
# 128 = 1,835,008 multiply-adds of the recurrence; the attention 3 x 2048 x
# 4096 + 2 x 2048 x 512 = 27,262,976; a routed block beside its experts:
# router 2048 x 512 + shared 3 x 2048 x 512 + its gate 2048 = 4,196,352,
# and 10 x 32 / 512 experts of 3 x 2048 x 512 = 3,145,728; the head 2048 x
# 18,992.


def test_required_terms_by_hand():
    sizes = _config()["sizes"]
    terms = plain.required_terms(sizes, 16384)
    mixer, rule = 33_685_504, 1_835_008
    attention = 27_262_976
    routed = 4_196_352 + 10 * 32 / 512 * 3_145_728
    head = 2048 * 18_992
    matrices = 3 * mixer + attention + 4 * routed + head
    assert matrices == 191_864_832
    assert terms["multiplied_params"] == matrices + 3 * rule
    # one full layer of 16 heads x 256 channels
    assert terms["attention_pair_channels"] == 4096 * 8192.5
    need = flops.resolve(_config(), 16384)
    assert need == 6.0 * (matrices + 3 * rule) + 12.0 * 4096 * 8192.5
    assert need == 1_586_896_896
    # ISSUE 63's 2.87 GFLOP was two periods without the recurrence
    two = plain.required_terms(
        dict(_config()["sizes"], layer_pattern="GeGeGe*e" * 2), 16384
    )
    assert flops.flops_of(two) == 2_940_420_096
    assert round((flops.flops_of(two) - 36 * rule) / 1e9, 2) == 2.87
    # the shares of one period: the mixers' matrices 38%, the full
    # layer's pairs 25%, its projections 10%, the routed blocks 9% (the
    # held experts 3.0%), the head 15%, the recurrence 2.1%
    assert round(18 * mixer / need, 2) == 0.38
    assert round(12 * 4096 * 8192.5 / need, 2) == 0.25
    assert round(6 * attention / need, 2) == 0.10
    assert round(24 * routed / need, 2) == 0.09
    assert round(24 * 10 * 32 / 512 * 3_145_728 / need, 3) == 0.030
    assert round(6 * head / need, 2) == 0.15
    assert round(18 * rule / need, 3) == 0.021


def test_required_terms_follow_the_pattern_and_the_share():
    sizes = _config()["sizes"]
    base = plain.required_terms(sizes, 16384)
    longer = plain.required_terms(
        dict(sizes, layer_pattern=sizes["layer_pattern"] + "Ge"), 16384
    )
    assert longer["multiplied_params"] - base["multiplied_params"] == int(
        33_685_504 + 1_835_008 + 4_196_352 + 10 * 32 / 512 * 3_145_728
    )
    every = plain.required_terms(dict(sizes, n_experts_held=512), 16384)
    # all 512 held: 10 whole experts a token, in 4 blocks
    assert every["multiplied_params"] - base["multiplied_params"] == int(
        4 * (10 - 10 * 32 / 512) * 3_145_728
    )


# ---- the file against the program and the source ----------------------------


def test_sizes_are_the_programs_model_with_its_overrides():
    from benchmarks.runners.train import _program_config

    config = _config()
    cfg = _program_config(config)  # raises on a size the program lacks
    assert cfg.layer_pattern == "GeGeGe*e" and cfg.n_layer == 4
    assert cfg.train_only.startswith("gated-delta-rule")
    assert cfg.num_params() == 625_667_136
    assert cfg.flops_per_token(16384) == flops.resolve(config, 16384)
    assert (cfg.head_dim, cfg.rope_dim) == (256, 64)
    from dlrover_tpu.models import get_config

    full = get_config(config["program"]["model"])
    assert full.layer_pattern[:8] == cfg.layer_pattern
    assert (full.n_layer, full.n_experts, full.vocab_size) == (
        48, 512, 151936
    )


def test_file_holds_the_published_keys_but_for_the_stated_cuts():
    config = _config()
    manifest = rehearsal._manifest()
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert sorted(entry["reduced"]) == sorted(config["reduced"])
    assert entry["file"] == f"benchmarks/configs/{CONFIG}.json"
    assert entry["source"] == config["source"]
    published = {
        "decoder_sparse_step": 1, "full_attention_interval": 4,
        "head_dim": 256, "hidden_size": 2048, "intermediate_size": 5120,
        "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128,
        "linear_num_key_heads": 16, "linear_num_value_heads": 32,
        "linear_value_head_dim": 128, "moe_intermediate_size": 512,
        "num_attention_heads": 16, "num_experts_per_tok": 10,
        "num_key_value_heads": 2, "partial_rotary_factor": 0.25,
        "rms_norm_eps": 1e-06, "rope_theta": 10000000,
        "shared_expert_intermediate_size": 512,
    }
    assert {k: config[k] for k in published} == published
    cut = {
        "num_hidden_layers": 4, "num_experts": 32, "vocab_size": 18992,
        "max_position_embeddings": 16384,
    }
    assert {k: config[k] for k in cut} == cut
    assert sorted(cut) == sorted(entry["reduced"])
    assert config["num_experts_published"] == 512
    # the floors: a whole period and four layers, eight experts, an
    # eighth of the vocabulary
    assert config["num_hidden_layers"] % config["full_attention_interval"] == 0
    assert config["num_experts"] >= 8 and 18992 * 8 == 151936
    sizes = config["sizes"]
    assert (sizes["n_experts"], sizes["n_experts_held"]) == (512, 32)
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "b1s16384", 1
    )
    assert len(manifest["workloads"]) == 11
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1


# ---- the rule's operations and bytes ----------------------------------------
# by hand at the cell's sizes. Operations: a token and value head 7 x 128
# x 128 = 114,688 forward and twice that backward, 344,064; x 32 heads x
# 16,384 tokens x 3 layers = 541,165,879,296 a step (2.75 ms at 197
# TFLOP/s). Bytes a token and layer, q, k, v and o float32 as the
# program hands them over: q and k 2 x 16 x 128 x 4 = 16,384, v 32 x 128
# x 4 = 16,384, g and β 2 x 32 x 4 = 256: operands 33,024; the forward
# adds o, 16,384: 49,408; the backward reads the operands and o's
# cotangent and writes five cotangents: 82,432; 131,840 x 16,384 x 3 =
# 6,480,199,680 a step (7.91 ms at 819 GB/s): the bytes bound.


def test_rule_operations_and_bytes_by_hand():
    sizes = _config()["sizes"]
    assert gdn.layers(sizes) == 3
    assert gdn.rule_operations(sizes, 16384) == 541_165_879_296
    assert gdn.rule_bytes(sizes, 16384) == 6_480_199_680
    assert gdn.rule_bytes(sizes, 1) == 3 * (49_408 + 82_432)
    chip = peaks.chip_peaks("TPU v5 lite")
    ops_s = gdn.rule_operations(sizes, 16384) / chip.bf16_flops
    bytes_s = gdn.rule_bytes(sizes, 16384) / chip.hbm_bytes_s
    assert round(1e3 * ops_s, 2) == 2.75 and round(1e3 * bytes_s, 2) == 7.91
    # the forward's operations are the reference's count of the
    # recurrence, two a multiply-add
    assert gdn.rule_operations(sizes, 1) == 3 * 2 * 3 * (
        plain.gdn_multiply_adds(sizes)
    )


# ---- the readers ------------------------------------------------------------
# rows as a traced step of the cell names them (op_names of the compiled
# text: forward, the layer's recomputation, a stretch's own, the backward)

STEP = "jit(step_fn)/jit(main)/"
BACK = STEP + "transpose(jvp(gdn))/"
BY_NAME = {
    "fusion.11 fusion bf16[1,16384,12288]": [0.30, 18],
    "conv_fwd.3 custom-call tpu_custom_call bf16[1,16384,8192]": [0.05, 12],
    "fusion.13 fusion f32[32,16,2,64,64]": [0.20, 144],
    "fusion.14 fusion f32[1,16,2,128,128]": [0.25, 4608],
    "fusion.15 fusion f32[1,16384,4096]": [0.04, 18],
    "fusion.16 fusion bf16[1,16384,2048]": [0.06, 18],
    "flash_fwd.1 custom-call tpu_custom_call bf16[16,16384,256]": [0.20, 6],
    "fusion.21 fusion bf16[1,16384,4096]": [0.03, 6],
    "fusion.31 fusion f32[16384,512]": [0.02, 24],
    "fusion.32 fusion s32[163840]": [0.05, 24],
    "ragged-dot-none.7 custom-call tpu_custom_call bf16[10240,512]": [0.1, 72],
    "fusion.34 fusion bf16[16384,2048]": [0.03, 24],
    "fusion.35 fusion bf16[16384,512]": [0.04, 24],
    "fusion.41 fusion bf16[16384,18992]": [0.3, 3],
}
OP_NAMES = {
    "fusion.11 fusion bf16[1,16384,12288]":
        STEP + "jvp(gdn)/checkpoint/dot_general",
    "conv_fwd.3 custom-call tpu_custom_call bf16[1,16384,8192]":
        STEP + "jvp(gdn)/checkpoint/gdn.conv/ssm.conv/pallas_call",
    "fusion.13 fusion f32[32,16,2,64,64]":
        BACK + "checkpoint/gdn.rule/while/body/checkpoint/exp",
    "fusion.14 fusion f32[1,16,2,128,128]":
        STEP + "jvp(gdn)/checkpoint/gdn.rule/while/body/while/body/dot_general",
    "fusion.15 fusion f32[1,16384,4096]":
        STEP + "jvp(gdn)/checkpoint/gdn.gate/mul",
    "fusion.16 fusion bf16[1,16384,2048]":
        BACK + "checkpoint/dot_general",
    "flash_fwd.1 custom-call tpu_custom_call bf16[16,16384,256]":
        STEP + "jvp(attn)/checkpoint/pallas_call",
    "fusion.21 fusion bf16[1,16384,4096]":
        STEP + "transpose(jvp(attn))/checkpoint/attn.gate/mul",
    "fusion.31 fusion f32[16384,512]":
        STEP + "jvp(mlp)/checkpoint/moe.route/dot_general",
    "fusion.32 fusion s32[163840]":
        STEP + "jvp(mlp)/checkpoint/moe.sort/sort",
    "ragged-dot-none.7 custom-call tpu_custom_call bf16[10240,512]":
        STEP + "jvp(mlp)/checkpoint/moe.experts/ragged_dot",
    "fusion.34 fusion bf16[16384,2048]":
        STEP + "transpose(jvp(mlp))/checkpoint/moe.combine/mul",
    "fusion.35 fusion bf16[16384,512]":
        STEP + "jvp(mlp)/checkpoint/moe.shared/dot_general",
    "fusion.41 fusion bf16[16384,18992]":
        STEP + "jvp(head_loss)/dot_general",
}


def _run(op_names=OP_NAMES, said=None, dispatches=3):
    first = {
        "busy_s": 2.0, "by_name": BY_NAME, "modules": ["jit_step_fn"],
        "op_names": {k: {v: BY_NAME[k][0]} for k, v in op_names.items()},
    }
    said = [] if said is None else said
    spans = Spans()
    with spans.span("traced_window"):
        for _ in range(dispatches):
            with spans.span("dispatch"):
                pass
    return {
        "trace": {"per_device": [first]}, "spans": spans,
        "say": lambda **record: said.append(record),
        "sizes": _config()["sizes"], "window": {"tokens": 16384},
        "peaks": peaks.chip_peaks("TPU v5 lite"),
    }


def _without(scope):
    return {
        k: v for k, v in OP_NAMES.items()
        if scope not in v.replace("(", "/").replace(")", "/").split("/")
    }


@pytest.mark.parametrize(
    "metric,scopes,rows,seconds",
    [
        ("gdn.mixer_share", ("gdn",), (6,), 0.90),
        ("gdn.rule_share", ("gdn.rule",), (2,), 0.45),
        ("gdn.attn_share", ("attn",), (2,), 0.23),
        ("gdn.moe_share", (
            "moe.route", "moe.sort", "moe.experts", "moe.combine",
            "moe.shared",
        ), (1, 1, 1, 1, 1), 0.24),
    ],
)
def test_scope_share_readers(metric, scopes, rows, seconds):
    read, said = _reader(metric), []
    assert read(_run(said=said)) == pytest.approx(100.0 * seconds / 2.0)
    assert [r["metric"] for r in said] == [metric]
    assert {k: v[0] for k, v in said[0]["rows"].items()} == dict(
        zip(scopes, rows)
    )
    assert sum(v[1] for v in said[0]["rows"].values()) == pytest.approx(
        seconds
    )
    assert read({"trace": None}) is None
    # a program without the scope (the parent): nothing read, nothing
    # raised, and the rows it did find still on the line
    for scope in scopes:
        assert read(_run(op_names=_without(scope))) is None


def test_rule_roofline_is_the_bytes_over_the_scopes_seconds():
    read = _reader("gdn.rule_roofline")
    # three traced steps: 3 x 7.91 ms of bytes over 0.45 s under gdn.rule
    floor = 3 * 6_480_199_680 / 819e9
    assert read(_run()) == pytest.approx(100.0 * floor / 0.45)
    assert read(_run()) == pytest.approx(5.275, rel=1e-3)
    # a rule as fast as the bytes allow reads 100, and nothing reads more
    quick = dict(BY_NAME)
    quick["fusion.13 fusion f32[32,16,2,64,64]"] = [floor / 2, 144]
    quick["fusion.14 fusion f32[1,16,2,128,128]"] = [floor / 2, 4608]
    run = _run()
    first = run["trace"]["per_device"][0]
    first["op_names"] = {
        k: {v: quick[k][0]} for k, v in OP_NAMES.items()
    }
    assert read(run) == pytest.approx(100.0)
    assert read({"trace": None}) is None
    assert read(_run(op_names=_without("gdn.rule"))) is None
    assert read(_run(dispatches=0)) is None


def test_held_rows_ratio_is_the_median_over_balanced_rows():
    read = _reader("gdn.held_rows_ratio")
    run = {
        "step_metrics": {"moe_held_rows": [9000.0, 10240.0, 11000.0]},
        "window": {"tokens": 16384}, "sizes": _config()["sizes"],
    }
    # 16,384 x 10 x 32 / 512 = 10,240 rows under balanced routing
    assert read(run) == 1.0
    assert read({"step_metrics": {}}) is None


def test_new_metrics_are_listed_for_this_cell_alone():
    manifest = rehearsal._manifest()
    for name in METRICS:
        entry = next(m for m in manifest["per_layer"] if m["name"] == name)
        assert entry["workloads"] == [CELL]
        assert entry["moves"] == "train_tokens_per_s"
        path = os.path.join(
            ROOT, "benchmarks", "layer_metrics", name + ".py"
        )
        assert os.path.exists(path)
    assert [m["name"] for m in manifest["per_layer"][-6:]] == list(METRICS)
    # what was there keeps its lists
    for name in ("ssm.scan_share", "moe.held_rows_ratio", "swa.gate_share"):
        entry = next(m for m in manifest["per_layer"] if m["name"] == name)
        assert CELL not in entry["workloads"]


# ---- run.py end to end at a tiny size ---------------------------------------

_OVERRIDES = {
    "n_layer": 8, "layer_pattern": "GeGeGe*e" * 2, "d_model": 128,
    "n_head": 4, "n_kv_head": 2, "d_head": 32, "vocab_size": 512,
    "max_seq": 128, "gdn_key_heads": 2, "gdn_value_heads": 4,
    "gdn_key_dim": 16, "gdn_value_dim": 16,
    "n_experts": 8, "expert_top_k": 2, "d_expert": 64,
    "d_shared_expert": 64, "n_experts_held": 4, "expert_offset": 0,
    "remat": "full", "attn_block_q": 128, "attn_block_k": 128,
}
TINY = {
    "source": "test",
    "program": {
        "model": "qwen3-next",
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
        norm="rmsnorm", norm_eps=1e-6, act="swiglu", pos="rope",
        tie_embeddings=False, conv_kernel=4, partial_rotary_factor=0.25,
        rope_theta=1e7, n_shared_experts=1, shared_expert_gate=True,
        moe_impl="ragged", moe_score="softmax", moe_renorm_topk=True,
        norm_zero_centered=True,
    ),
    "reference": "qwen3_next_plain",
    "check": {"kind": "routed"},
}
CHECKS = [
    "choices_valid", "routing_regret", "logits_vs_reference",
    "logits_rms_vs_reference", "loss_vs_reference",
    "gdn_readout_ms_vs_reference", "loss_vs_free_reference",
    "first_step_loss", "no_compile_in_window", "no_failed_step",
]


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
        monkeypatch, capsys, TINY, trace, seed=rehearsal.ROUTED_SEED
    )
    assert rc == 0 and cell["name"] == CELL
    result = json.loads(lines[-1])
    checks, events = rehearsal._events(lines)
    assert list(checks) == CHECKS
    assert all(c["ok"] for c in checks.values()), checks
    assert result["correct"] is True and result["failed"] == 0
    ref = events["reference"]
    # one row of choices a routed block: every layer has one
    assert len(ref["moved_by_layer"]) == 8
    assert set(ref["reference_terms"]) == {"gdn_readout_ms"}
    assert ref["forced_logit_err"] < 1e-4
    if not trace:
        assert set(result["metrics"]) == {"train_tokens_per_s", "setup_s"}
        return
    listed = {
        m["name"] for m in manifest["per_layer"]
        if "workloads" not in m or CELL in m["workloads"]
    }
    assert set(METRICS) <= listed
    # no device plane on the CPU: the trace readers return nothing and
    # the line leaves them out; the program's counter is read
    assert set(result["metrics"]) <= listed
    assert "gdn.rule_share" not in result["metrics"]
    assert "gdn.held_rows_ratio" in result["metrics"]


@pytest.mark.parametrize("defect", sorted(defects.PLANT))
def test_comparison_fails(monkeypatch, capsys, defect):
    """Sound, the tiny cell reads 1e-6 on the logits; each defect has to
    push a check past the CHIP's limits (4e-2 at the maximum, 2.5e-2
    rms, 2e-3 on the read-out), which are the ones ``run.py`` holds."""
    _this_cell_first(monkeypatch)
    defects.PLANT[defect](monkeypatch.setattr)
    rc, _, _, lines = rehearsal._run_patched(
        monkeypatch, capsys, TINY, 0, seed=rehearsal.ROUTED_SEED
    )
    assert rc == 0
    checks, _ = rehearsal._events(lines)
    failed = {name for name, c in checks.items() if not c["ok"]}
    assert failed & set(defects.CAUGHT_BY[defect]), (defect, checks)
    assert json.loads(lines[-1])["correct"] is False
