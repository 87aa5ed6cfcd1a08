"""What PR 65 adds to the benchmark, on the CPU: the required FLOPs of
``references/kimi_linear_plain.py`` by hand, the committed file's
``sizes`` against the program's model with its overrides and against the
catalog's published keys, the vector rule's operations and bytes and the
latent flash's required work by hand, the seven new readers on a
recorded ``op_names`` table (and on a program without the scopes, where
they read nothing), ``run.py`` end to end at a tiny size of this
architecture, and the defects of ``kimilinear_defects.py``, each of which
the comparison has to fail."""

import json
import os

import pytest

from benchmarks.lib import flops, kda, peaks
from benchmarks.lib.spans import Spans
from benchmarks.references import kimi_linear_plain as plain
from benchmarks.tests import kimilinear_defects as defects
from benchmarks.tests import test_rehearsal as rehearsal
from benchmarks.tests.test_zero_readers import _reader

ROOT = rehearsal.ROOT
CELL = "kimilinear-ep16-train-b1s16384"
CONFIG = "kimi-linear-48b-a3b-ep16-1chip"
METRICS = (
    "kda.mixer_share", "kda.rule_share", "kda.rule_roofline",
    "kda.mla_share", "kda.mla_flash_roofline", "kda.moe_share",
    "kda.held_rows_ratio",
)


def _config():
    path = os.path.join(ROOT, "benchmarks", "configs", CONFIG + ".json")
    with open(path) as f:
        return json.load(f)


# ---- required FLOPs ---------------------------------------------------------
# per part, by hand (the published widths): a mixer 2304 x (3 x 4096 + 2 x
# 128 + 32) + 2 x 128 x 4096 + 4096 x 2304 = 39,460,864 in its matrices and
# 3.5 x 32 x 128 x 128 = 1,835,008 multiply-adds of the recurrence; the
# latent attention 2304 x 6144 + 2304 x 576 + 512 x 8192 + 4096 x 2304 =
# 29,114,368; the dense MLP 3 x 2304 x 9216 = 63,700,992; a routed block
# beside its experts: router 2304 x 256 + shared 3 x 2304 x 1024 =
# 7,667,712, and 8 x 16 / 256 experts of 7,077,888; the head 2304 x 20,480.


def test_required_terms_by_hand():
    sizes = _config()["sizes"]
    terms = plain.required_terms(sizes, 16384)
    mixer, rule = 39_460_864, 1_835_008
    attention, dense = 29_114_368, 63_700_992
    routed = 7_667_712 + 8 * 16 / 256 * 7_077_888
    head = 2304 * 20_480
    matrices = 4 * mixer + attention + dense + 4 * routed + head
    assert matrices == 342_671_360
    assert terms["multiplied_params"] == matrices + 4 * rule == 350_011_392
    # one latent layer of 32 heads: a pair costs a product over 192
    # score and one over 128 value channels, 160 at the mean
    assert terms["attention_pair_channels"] == 32 * 160 * 8192.5
    need = flops.resolve(_config(), 16384)
    assert need == 6.0 * 350_011_392 + 12.0 * 32 * 160 * 8192.5
    assert need == 2_603_415_552
    # ISSUE 65's shares: the mixers' matrices 36%, the latent layer's
    # pairs 19% and matrices 7%, routers, shared and held experts 10%
    # (the held alone 3.3%), the dense MLP 15%, the head 11%, the rule 1.7%
    assert round(24 * mixer / need, 2) == 0.36
    assert round(12 * 32 * 160 * 8192.5 / need, 2) == 0.19
    assert round(6 * attention / need, 2) == 0.07
    assert round(24 * routed / need, 2) == 0.10
    assert round(24 * 8 * 16 / 256 * 7_077_888 / need, 3) == 0.033
    assert round(6 * dense / need, 2) == 0.15
    assert round(6 * head / need, 2) == 0.11
    assert round(24 * rule / need, 3) == 0.017


def test_required_terms_follow_the_pattern_and_the_share():
    sizes = _config()["sizes"]
    base = plain.required_terms(sizes, 16384)
    longer = plain.required_terms(
        dict(sizes, layer_pattern=sizes["layer_pattern"] + "Ke"), 16384
    )
    assert longer["multiplied_params"] - base["multiplied_params"] == int(
        39_460_864 + 1_835_008 + 7_667_712 + 8 * 16 / 256 * 7_077_888
    )
    every = plain.required_terms(dict(sizes, n_experts_held=256), 16384)
    # all 256 held: 8 whole experts a token, in 4 blocks
    assert every["multiplied_params"] - base["multiplied_params"] == int(
        4 * (8 - 8 * 16 / 256) * 7_077_888
    )
    two = plain.required_terms(
        dict(sizes, layer_pattern=sizes["layer_pattern"] + "*e"), 16384
    )
    assert two["attention_pair_channels"] == 2 * 32 * 160 * 8192.5


# ---- the file against the program and the source ----------------------------


def test_sizes_are_the_programs_model_with_its_overrides():
    from benchmarks.runners.train import _program_config

    config = _config()
    cfg = _program_config(config)  # raises on a size the program lacks
    assert cfg.layer_pattern == "K-KeKe*eKe" and cfg.n_layer == 5
    assert "key channel" in cfg.train_only
    assert cfg.num_params() == 828_925_824
    assert cfg.flops_per_token(16384) == flops.resolve(config, 16384)
    assert (cfg.head_dim, cfg.value_dim, cfg.q_lora_rank) == (192, 128, 0)
    from dlrover_tpu.models import get_config

    full = get_config(config["program"]["model"])
    assert full.layer_pattern[:10] == cfg.layer_pattern
    assert (full.n_layer, full.n_experts, full.vocab_size) == (
        27, 256, 163840
    )


def test_file_holds_the_published_keys_but_for_the_stated_cuts():
    config = _config()
    manifest = rehearsal._manifest()
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert sorted(entry["reduced"]) == sorted(config["reduced"])
    assert entry["file"] == f"benchmarks/configs/{CONFIG}.json"
    assert entry["source"] == config["source"]
    published = {
        "first_k_dense_replace": 1, "head_dim": 72, "hidden_size": 2304,
        "intermediate_size": 9216, "kv_lora_rank": 512,
        "mla_use_nope": True, "moe_intermediate_size": 1024,
        "moe_layer_freq": 1, "moe_renormalize": True,
        "num_attention_heads": 32, "num_expert_group": 1,
        "num_experts_per_token": 8, "num_key_value_heads": 32,
        "num_nextn_predict_layers": 0, "num_shared_experts": 1,
        "q_lora_rank": None, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05, "rope_theta": 10000,
        "routed_scaling_factor": 2.446, "topk_group": 1, "v_head_dim": 128,
    }
    assert {k: config[k] for k in published} == published
    linear = config["linear_attn_config"]
    assert (linear["head_dim"], linear["num_heads"]) == (128, 32)
    assert linear["short_conv_kernel_size"] == 4
    assert linear["full_attn_layers"] == [4, 8, 12, 16, 20, 24, 27]
    assert linear["kda_layers"][:4] == [1, 2, 3, 5]
    cut = {
        "num_hidden_layers": 5, "num_experts": 16, "vocab_size": 20480,
        "model_max_length": 16384,
    }
    assert {k: config[k] for k in cut} == cut
    assert sorted(cut) == sorted(entry["reduced"])
    assert config["num_experts_published"] == 256
    # the floors: the dense layer and a whole period, eight experts or
    # more, an eighth of the vocabulary
    assert config["num_experts"] >= 8 and 20480 * 8 == 163840
    sizes = config["sizes"]
    assert (sizes["n_experts"], sizes["n_experts_held"]) == (256, 16)
    assert (sizes["kda_heads"], sizes["kda_head_dim"]) == (32, 128)
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "b1s16384", 1
    )
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1
    for key in ("deployment", "parameters", "assumed"):
        assert config[key]


# ---- the rule's operations and bytes, the flash kernels' ---------------------
# by hand at the cell's sizes. Operations: a token and head 7 x 128 x 128
# = 114,688 forward and twice that backward, 344,064; x 32 heads x 16,384
# tokens x 4 layers = 721,554,505,728 a step (3.66 ms at 197 TFLOP/s).
# Bytes a token and layer, float32: q, k, v and g 4 x 32 x 128 x 4 =
# 65,536 (g as wide as k: a decay a key channel), β 128: operands 65,664;
# the forward adds o, 16,384: 82,048; the backward reads the operands and
# o's cotangent and writes five cotangents: 147,712; 229,760 x 16,384 x 4
# = 15,057,551,360 a step (18.39 ms at 819 GB/s): the bytes bound.


def test_rule_operations_and_bytes_by_hand():
    sizes = _config()["sizes"]
    assert kda.layers(sizes) == 4
    assert kda.rule_operations(sizes, 16384) == 721_554_505_728
    assert kda.rule_bytes(sizes, 16384) == 15_057_551_360
    assert kda.rule_bytes(sizes, 1) == 4 * (82_048 + 147_712)
    chip = peaks.chip_peaks("TPU v5 lite")
    ops_s = kda.rule_operations(sizes, 16384) / chip.bf16_flops
    bytes_s = kda.rule_bytes(sizes, 16384) / chip.hbm_bytes_s
    assert round(1e3 * ops_s, 2) == 3.66 and round(1e3 * bytes_s, 2) == 18.39
    # the forward's operations are the reference's count of the
    # recurrence, two a multiply-add
    assert kda.rule_operations(sizes, 1) == 4 * 2 * 3 * (
        plain.kda_multiply_adds(sizes)
    )


def test_flash_calls_are_counted_at_192_and_128():
    """A forward call: q kᵀ over 192 and p v over 128 channels, 2 x 320
    operations a useful pair and head; dq 2 x (2 x 192 + 128), dkv 2 x
    (2 x 192 + 2 x 128). Padded to 192 throughout the program executes
    2 x 384, 2 x 576 and 2 x 768: the count stays under it."""
    sizes = _config()["sizes"]
    pairs = 32 * 16384 * 8192.5
    assert kda.flash_call_flops("flash_fwd", sizes, 1, 16384) == (
        2 * 320 * pairs
    )
    assert kda.flash_call_flops("flash_bwd_dq", sizes, 1, 16384) == (
        2 * 512 * pairs
    )
    assert kda.flash_call_flops("flash_bwd_dkv", sizes, 1, 16384) == (
        2 * 640 * pairs
    )
    rows = 16384 * 32 * 2
    assert kda.flash_call_bytes("flash_fwd", sizes, 1, 16384) == rows * 640
    assert kda.flash_call_bytes("flash_bwd_dkv", sizes, 1, 16384) == (
        rows * 960
    )


# ---- the readers ------------------------------------------------------------
# rows as a traced step of the cell names them (op_names of the compiled
# text: forward, the layer's recomputation, a stretch's own, the backward)

STEP = "jit(step_fn)/jit(main)/"
BACK = STEP + "transpose(jvp(kda))/"
BY_NAME = {
    "fusion.11 fusion f32[1,16384,12288]": [0.30, 24],
    "conv_fwd.3 custom-call tpu_custom_call f32[1,16384,12288]": [0.05, 16],
    "fusion.13 fusion f32[1,16,32,4,16,16,128]": [0.40, 256],
    "fusion.14 fusion f32[1,32,128,128]": [0.50, 6144],
    "fusion.15 fusion f32[1,16384,4096]": [0.04, 24],
    "fusion.16 fusion bf16[1,16384,2304]": [0.06, 24],
    "flash_fwd.1 custom-call tpu_custom_call bf16[32,16384,192]": [0.05, 3],
    "flash_bwd_dq.1 custom-call tpu_custom_call bf16[32,16384,192]":
        [0.08, 3],
    "flash_bwd_dkv.1 custom-call tpu_custom_call bf16[32,16384,192]":
        [0.10, 3],
    "fusion.21 fusion bf16[1,16384,32,192]": [0.03, 6],
    "fusion.31 fusion f32[16384,256]": [0.02, 24],
    "fusion.32 fusion s32[131072]": [0.05, 24],
    "ragged-dot-none.7 custom-call tpu_custom_call bf16[8192,1024]":
        [0.1, 72],
    "fusion.33 fusion bf16[131072,1024]": [0.02, 24],
    "fusion.34 fusion bf16[16384,2304]": [0.03, 24],
    "fusion.35 fusion bf16[16384,1024]": [0.04, 24],
    "fusion.41 fusion bf16[16384,20480]": [0.3, 3],
}
OP_NAMES = {
    "fusion.11 fusion f32[1,16384,12288]":
        STEP + "jvp(kda)/checkpoint/dot_general",
    "conv_fwd.3 custom-call tpu_custom_call f32[1,16384,12288]":
        STEP + "jvp(kda)/checkpoint/kda.conv/ssm.conv/pallas_call",
    "fusion.13 fusion f32[1,16,32,4,16,16,128]":
        BACK + "checkpoint/kda.rule/while/body/checkpoint/exp",
    "fusion.14 fusion f32[1,32,128,128]":
        STEP + "jvp(kda)/checkpoint/kda.rule/while/body/while/body/dot_general",
    "fusion.15 fusion f32[1,16384,4096]":
        STEP + "jvp(kda)/checkpoint/kda.gate/mul",
    "fusion.16 fusion bf16[1,16384,2304]":
        BACK + "checkpoint/dot_general",
    "flash_fwd.1 custom-call tpu_custom_call bf16[32,16384,192]":
        STEP + "jvp(attn)/checkpoint/flash_fwd/pallas_call",
    "flash_bwd_dq.1 custom-call tpu_custom_call bf16[32,16384,192]":
        STEP + "transpose(jvp(attn))/checkpoint/flash_bwd_dq/pallas_call",
    "flash_bwd_dkv.1 custom-call tpu_custom_call bf16[32,16384,192]":
        STEP + "transpose(jvp(attn))/checkpoint/flash_bwd_dkv/pallas_call",
    "fusion.21 fusion bf16[1,16384,32,192]":
        STEP + "jvp(attn)/checkpoint/attn.latent/pad",
    "fusion.31 fusion f32[16384,256]":
        STEP + "jvp(mlp)/checkpoint/moe.route/dot_general",
    "fusion.32 fusion s32[131072]":
        STEP + "jvp(mlp)/checkpoint/moe.sort/sort",
    # the compiler's own call: its op_name names no scope of the program
    "ragged-dot-none.7 custom-call tpu_custom_call bf16[8192,1024]":
        "ragged-dot-none",
    "fusion.33 fusion bf16[131072,1024]":
        STEP + "jvp(mlp)/checkpoint/moe.experts/mul",
    "fusion.34 fusion bf16[16384,2304]":
        STEP + "transpose(jvp(mlp))/checkpoint/moe.combine/mul",
    "fusion.35 fusion bf16[16384,1024]":
        STEP + "jvp(mlp)/checkpoint/moe.shared/dot_general",
    "fusion.41 fusion bf16[16384,20480]":
        STEP + "jvp(head_loss)/dot_general",
}


def _run(op_names=OP_NAMES, said=None, dispatches=3, by_name=BY_NAME):
    first = {
        "busy_s": 4.0, "by_name": by_name, "modules": ["jit_step_fn"],
        "op_names": {k: {v: by_name[k][0]} for k, v in op_names.items()},
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
        "seq": 16384, "peaks": peaks.chip_peaks("TPU v5 lite"),
    }


def _without(scope):
    return {
        k: v for k, v in OP_NAMES.items()
        if scope not in v.replace("(", "/").replace(")", "/").split("/")
    }


@pytest.mark.parametrize(
    "metric,scopes,rows,seconds",
    [
        ("kda.mixer_share", ("kda",), (6,), 1.35),
        ("kda.rule_share", ("kda.rule",), (2,), 0.90),
        ("kda.mla_share", ("attn",), (4,), 0.26),
    ],
)
def test_scope_share_readers(metric, scopes, rows, seconds):
    read, said = _reader(metric), []
    assert read(_run(said=said)) == pytest.approx(100.0 * seconds / 4.0)
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


def test_moe_share_adds_the_grouped_matmuls_by_their_label():
    """The five scopes' rows and the ``ragged-dot`` calls, which the
    compiler leaves under no scope; one that does carry a scope (a
    compiler that keeps the path) counts once."""
    scopes = (
        "moe.route", "moe.sort", "moe.experts", "moe.combine", "moe.shared",
    )
    read, said = _reader("kda.moe_share"), []
    assert read(_run(said=said)) == pytest.approx(100.0 * (0.16 + 0.1) / 4.0)
    assert [r["event"] for r in said] == ["scope_rows", "ragged_dot_rows"]
    assert {k: v[0] for k, v in said[0]["rows"].items()} == dict(
        zip(scopes, (1, 1, 1, 1, 1))
    )
    assert sum(v[1] for v in said[0]["rows"].values()) == pytest.approx(0.16)
    assert said[1]["rows"] == [1, pytest.approx(0.1)]
    kept = dict(OP_NAMES)
    kept["ragged-dot-none.7 custom-call tpu_custom_call bf16[8192,1024]"] = (
        STEP + "jvp(mlp)/checkpoint/moe.experts/ragged_dot"
    )
    assert read(_run(op_names=kept)) == pytest.approx(100.0 * 0.26 / 4.0)
    assert read({"trace": None}) is None
    for scope in scopes:
        assert read(_run(op_names=_without(scope))) is None


def test_rule_roofline_is_the_bytes_over_the_scopes_seconds():
    read = _reader("kda.rule_roofline")
    # three traced steps: 3 x 18.39 ms of bytes over 0.9 s under kda.rule
    floor = 3 * 15_057_551_360 / 819e9
    assert read(_run()) == pytest.approx(100.0 * floor / 0.90)
    assert read(_run()) == pytest.approx(6.128, rel=1e-3)
    # a rule as fast as the bytes allow reads 100, and nothing reads more
    quick = dict(BY_NAME)
    quick["fusion.13 fusion f32[1,16,32,4,16,16,128]"] = [floor / 2, 256]
    quick["fusion.14 fusion f32[1,32,128,128]"] = [floor / 2, 6144]
    assert read(_run(by_name=quick)) == pytest.approx(100.0)
    assert read({"trace": None}) is None
    assert read(_run(op_names=_without("kda.rule"))) is None
    assert read(_run(dispatches=0)) is None


def test_flash_roofline_is_the_required_work_over_the_kernels_seconds():
    read = _reader("kda.mla_flash_roofline")
    pairs = 32 * 16384 * 8192.5
    # three calls of each kernel, 2 x (320 + 512 + 640) a pair
    need = 3 * 2 * 1472 * pairs / 197e12
    assert read(_run()) == pytest.approx(100.0 * need / 0.23)
    # kernels that run at the peak ON PADDED VALUES (192 in every
    # product: 384 + 576 + 768 a pair) read the required share of it
    padded = 3 * 2 * 1728 * pairs / 197e12
    at_peak = dict(BY_NAME)
    for label in BY_NAME:
        if label.startswith("flash"):
            at_peak[label] = [padded / 3, 3]
    assert read(_run(by_name=at_peak)) == pytest.approx(100 * 1472 / 1728)
    assert read({"trace": None}) is None
    no_flash = {k: v for k, v in BY_NAME.items() if not k.startswith("flash")}
    assert read(_run(
        by_name=no_flash,
        op_names={k: v for k, v in OP_NAMES.items() if k in no_flash},
    )) is None


def test_held_rows_ratio_is_the_median_over_balanced_rows():
    read = _reader("kda.held_rows_ratio")
    run = {
        "step_metrics": {"moe_held_rows": [8000.0, 8192.0, 9000.0]},
        "window": {"tokens": 16384}, "sizes": _config()["sizes"],
    }
    # 16,384 x 8 x 16 / 256 = 8,192 rows under balanced routing
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
    names = [m["name"] for m in manifest["per_layer"]]
    assert names[names.index(METRICS[0]):][:7] == list(METRICS)
    layers = {m["name"]: m["layer"] for m in manifest["per_layer"]}
    assert layers["kda.mla_share"] == layers["mla.flash_roofline"]
    assert layers["kda.moe_share"] == layers["moe.held_rows_ratio"]
    # what was there keeps its lists
    for name in ("gdn.rule_roofline", "mla.flash_roofline",
                 "moe.held_rows_ratio"):
        entry = next(m for m in manifest["per_layer"] if m["name"] == name)
        assert CELL not in entry["workloads"]


# ---- run.py end to end at a tiny size ---------------------------------------

_OVERRIDES = {
    "n_layer": 5, "layer_pattern": "K-KeKe*eKe", "d_model": 128,
    "d_ff": 192, "n_head": 4, "n_kv_head": 4, "vocab_size": 512,
    "max_seq": 128, "kda_heads": 4, "kda_head_dim": 16, "kda_gate_rank": 16,
    "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "n_experts": 8, "expert_top_k": 2, "d_expert": 64,
    "n_experts_held": 4, "expert_offset": 0, "remat": "full",
    "attn_block_q": 128, "attn_block_k": 128,
}
TINY = {
    "source": "test",
    "program": {
        "model": "kimi-linear",
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
        norm="rmsnorm", norm_eps=1e-5, act="swiglu", pos="none",
        tie_embeddings=False, conv_kernel=4, q_lora_rank=0,
        n_shared_experts=1, moe_impl="ragged", moe_score="sigmoid",
        moe_renorm_topk=True, routed_scaling_factor=2.446,
    ),
    "reference": "kimi_linear_plain",
    "check": {"kind": "routed"},
}
CHECKS = [
    "choices_valid", "routing_regret", "logits_vs_reference",
    "logits_rms_vs_reference", "loss_vs_reference",
    "kda_readout_ms_vs_reference", "loss_vs_free_reference",
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
    # one row of choices a routed block: every layer but the first
    assert len(ref["moved_by_layer"]) == 4
    assert set(ref["reference_terms"]) == {"kda_readout_ms"}
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
    assert "kda.rule_share" not in result["metrics"]
    assert "kda.held_rows_ratio" in result["metrics"]


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
