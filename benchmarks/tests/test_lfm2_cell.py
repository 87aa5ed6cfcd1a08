"""What PR 73 adds to the benchmark, on the CPU: the required FLOPs of
``references/lfm2_moe_plain.py`` by hand, the committed file's ``sizes``
against the program's model with its overrides and against the
catalog's published keys, the nine new readers on a canned ``op_names``
table (a missing scope reads NOTHING and raises nothing; no share can
pass 100), and ``run.py`` end to end at a tiny size of this
architecture, sound and with each planted defect."""

import json
import os

import pytest

from benchmarks.lib import flops, lfm2, peaks
from benchmarks.lib.spans import Spans
from benchmarks.references import lfm2_moe_plain as plain
from benchmarks.tests import lfm2_defects as defects
from benchmarks.tests import test_rehearsal as rehearsal
from benchmarks.tests.test_zero_readers import _reader

ROOT = rehearsal.ROOT
CELL = "lfm2-ep4-train-b8s4096"
CONFIG = "lfm2-8b-a1b-ep4-1chip"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
METRICS = [
    "lfm2.conv_mixer_share", "lfm2.gated_conv_share",
    "lfm2.gated_conv_roofline", "lfm2.attn_share", "lfm2.flash_roofline",
    "lfm2.dense_mlp_share", "lfm2.moe_share", "lfm2.grouped_matmul_roofline",
    "lfm2.held_rows_ratio",
]


def _config():
    path = os.path.join(ROOT, "benchmarks", "configs", CONFIG + ".json")
    with open(path) as f:
        return json.load(f)


# ---- required FLOPs ---------------------------------------------------------
# by hand (LFM2's widths): a conv mixer 2048 x 6144 + 2048 x 2048 =
# 16,777,216; the attention q and o 2 x 2048 x 2048 + k and v 2 x 2048 x
# 512 = 10,485,760; the dense MLP 3 x 2048 x 7168 = 44,040,192; the router
# 2048 x 32 = 65,536; one expert 3 x 2048 x 1792 = 11,010,048, of which a
# token meets 4 x 8 / 32 = 1 on a chip that holds 8 of 32. The head 2048 x
# 16,384 = 33,554,432. Pairs a query at 4,096: 2,048.5.


def test_required_terms_by_hand():
    config = _config()
    terms = plain.required_terms(config["sizes"], 4096)
    conv, attn, mlp, routed = 16_777_216, 10_485_760, 44_040_192, 11_075_584
    assert terms["multiplied_params"] == (
        2 * (conv + mlp) + (attn + routed) + 3 * (conv + routed) + 33_554_432
    ) == 260_308_992
    assert terms["attention_pair_channels"] == 2048 * 2048.5 == 4_195_328
    total = flops.resolve(config, 4096)
    assert total == 1_612_197_888
    # the shares the cell's ``why`` and PERF.md state
    share = lambda n: round(100 * n / total)  # noqa: E731
    assert share(6 * 5 * conv) == 31
    assert share(6 * 2 * mlp) == 33
    assert share(6 * 4 * 11_010_048) == 16
    assert share(6 * 33_554_432) == 12
    assert share(6 * attn + 12 * 4_195_328) == 7
    # the whole depth, every expert held: the count follows the sizes
    whole = dict(
        config["sizes"], n_layer=24, n_experts_held=32,
        layer_pattern="C-C-" + "*eCeCeCe" * 4 + "*eCeCe" * 2,
    )
    assert plain.required_terms(whole, 4096)["multiplied_params"] == (
        18 * conv + 6 * attn + 2 * mlp + 22 * (65_536 + 4 * 11_010_048)
        + 33_554_432
    )


def test_sizes_are_the_programs_model_with_its_overrides():
    from benchmarks.runners.train import _program_config
    from dlrover_tpu.models import get_config

    config = _config()
    cfg = _program_config(config)  # raises on a size the program lacks
    assert cfg.layer_pattern == "C-C-*eCeCeCe" and not cfg.n_dense_layer
    assert cfg.train_only.startswith("gated-short-convolution (C) layers")
    # ``norm_eps`` is the one size the runner does not hold the program
    # to: held here
    assert cfg.norm_eps == config["sizes"]["norm_eps"] == 1e-5
    assert cfg.flops_per_token(4096) == flops.resolve(config, 4096)
    assert cfg.num_params() == 568_647_808
    full = get_config(config["program"]["model"])
    assert full.layer_pattern[:12] == cfg.layer_pattern
    assert (full.n_layer, full.n_experts, full.vocab_size, full.max_seq) == (
        24, 32, 65536, 128000
    )
    assert config["reference"] == "lfm2_moe_plain"
    assert config["check"] == {"kind": "routed"}
    mellum = os.path.join(
        ROOT, "benchmarks", "configs", "mellum2-12b-a2.5b-ep4-1chip.json"
    )
    with open(mellum) as f:
        assert config["program"]["optimizer"] == json.load(f)["program"][
            "optimizer"
        ]


def test_file_holds_the_catalog_row_but_for_the_stated_cuts():
    config = _config()
    manifest = rehearsal._manifest()
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert sorted(entry["reduced"]) == sorted(config["reduced"])
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(CATALOG) as f:
        row = next(
            r for r in map(json.loads, f) if r["name"] == "LFM2-8B-A1B"
        )
    assert entry["source"] == config["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items() if config.get(k) != v}
    assert differs == set(entry["reduced"])
    cut = {
        "num_hidden_layers": 6, "num_experts": 8, "vocab_size": 16384,
        "max_position_embeddings": 4096,
    }
    assert {k: config[k] for k in cut} == cut
    # no width among the cuts, and the program's sizes are the published
    widths = {
        "d_model": "hidden_size", "n_head": "num_attention_heads",
        "n_kv_head": "num_key_value_heads", "d_ff": "intermediate_size",
        "d_expert": "moe_intermediate_size",
        "expert_top_k": "num_experts_per_tok", "conv_kernel": "conv_L_cache",
        "norm_eps": "norm_eps", "moe_renorm_topk": "norm_topk_prob",
        "rope_theta": "rope_theta",
        "routed_scaling_factor": "routed_scaling_factor",
    }
    sizes = config["sizes"]
    for ours, theirs in widths.items():
        assert sizes[ours] == row["config"][theirs], ours
    assert sizes["head_dim"] == (
        row["config"]["hidden_size"] // row["config"]["num_attention_heads"]
    )
    assert sizes["n_experts"] == row["config"]["num_experts"]
    assert config["num_experts_published"] == row["config"]["num_experts"]
    # the depth run is the published layers' first six, dense ones first
    kinds = "".join(
        {"conv": "C", "full_attention": "*"}[k] for k in config["layer_types"]
    )
    assert config["layer_types"] == row["config"]["layer_types"]
    assert kinds[:6] == sizes["layer_pattern"][::2] == "CC*CCC"
    assert sizes["layer_pattern"][1::2] == "--eeee"
    assert row["config"]["num_dense_layers"] == 2 == config["num_dense_layers"]
    # every item the published config has no key for is under ``assumed``
    assert {
        "tie_word_embeddings", "expert_bias", "renormalisation_guard",
        "router", "qk_layernorm", "rope_pairing", "softmax_scale", "conv",
        "training_context", "weights", "param_dtype", "optimizer",
    } <= set(config["assumed"])


def test_manifest_lists_the_cell_and_its_nine_metrics():
    manifest = rehearsal._manifest()
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "b8s4096", 1
    )
    assert len(cell["why"]) <= 200
    with open(os.path.join(ROOT, "benchmarks", "traffic", "b8s4096.json")) as f:
        traffic = json.load(f)
    with open(os.path.join(ROOT, "benchmarks", "traffic", "b1s32768.json")) as f:
        longer = json.load(f)
    assert (traffic["global_batch"], traffic["seq"]) == (8, 4096)
    others = ("seq", "global_batch", "what")
    assert {k: v for k, v in traffic.items() if k not in others} == {
        k: v for k, v in longer.items() if k not in others
    }
    ours = [m for m in manifest["per_layer"] if m["name"].startswith("lfm2.")]
    assert [m["name"] for m in ours] == METRICS
    for m in ours:
        assert m["workloads"] == [CELL]
        assert m["moves"] == "train_tokens_per_s"
        assert m["layer"] in (
            "gated short convolution", "attention", "train step",
            "routed experts",
        )
        assert (m["unit"] == "%") == (m["name"] != "lfm2.held_rows_ratio")
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1


# ---- the readers ------------------------------------------------------------

FWD = "jit(step_fn)/jvp()/checkpoint/"
RUN = "jit(step_fn)/jvp()/while/body/checkpoint/"
BACK = "jit(step_fn)/transpose(jvp())/checkpoint/"
GATED = " custom-call tpu_custom_call bf16[8,4096,2048]"
FLASH = " custom-call tpu_custom_call bf16[256,4096,64]"
RAGGED = "ragged-dot-none.9 custom-call tpu_custom_call bf16[131072,1792]"
BY_NAME = {
    # three traced steps, five conv layers: a forward and a recomputed
    # forward each (the scanned pair's one body 12 calls), one backward
    "gated_conv_fwd.1" + GATED: [0.0096, 12],
    "gated_conv_fwd.2" + GATED: [0.0144, 18],
    "gated_conv_bwd.3 custom-call tpu_custom_call bf16[8,4096,6144]":
        [0.0225, 15],
    "fusion.4 fusion bf16[8,4096,6144]": [0.20, 45],
    "fusion.5 fusion f32[8,4096,2048]": [0.10, 45],
    "flash_fwd.6" + FLASH: [0.034, 3],
    "flash_bwd_dq.7" + FLASH: [0.052, 3],
    "flash_bwd_dkv.8" + FLASH: [0.051, 3],
    "fusion.10 fusion bf16[8,4096,2048]": [0.06, 9],
    RAGGED: [0.36, 144],
    "ragged-dot-metadata.11 custom-call": [0.003, 36],
    "fusion.12 fusion f32[32768,32]": [0.01, 12],
    "fusion.13 fusion bf16[131072,2048]": [0.13, 12],
    "fusion.14 fusion bf16[32768,1792]": [0.05, 12],
    "fusion.15 fusion bf16[32768,2048]": [0.06, 12],
    "fusion.16 fusion bf16[2,1,2048,7168]": [0.40, 18],
    "norm_fwd.17 custom-call tpu_custom_call bf16[8,4096,2048]": [0.004, 24],
}
OP_NAMES = {
    "gated_conv_fwd.1" + GATED: RUN + "conv/conv.gate/pallas_call",
    "gated_conv_fwd.2" + GATED: FWD + "conv/conv.gate/pallas_call",
    "gated_conv_bwd.3 custom-call tpu_custom_call bf16[8,4096,6144]":
        BACK + "conv/conv.gate/pallas_call",
    "fusion.4 fusion bf16[8,4096,6144]": FWD + "conv/conv.in_proj/dot_general",
    "fusion.5 fusion f32[8,4096,2048]": FWD + "conv/conv.out_proj/dot_general",
    "flash_fwd.6" + FLASH: FWD + "attn/flash_fwd/pallas_call",
    "flash_bwd_dq.7" + FLASH: BACK + "attn/flash_bwd_dq/pallas_call",
    "flash_bwd_dkv.8" + FLASH: BACK + "attn/flash_bwd_dkv/pallas_call",
    "fusion.10 fusion bf16[8,4096,2048]": FWD + "attn/attn.rope/mul",
    "fusion.12 fusion f32[32768,32]": FWD + "mlp/moe.route/logistic",
    "fusion.13 fusion bf16[131072,2048]": FWD + "mlp/moe.sort/gather",
    "fusion.14 fusion bf16[32768,1792]": FWD + "mlp/moe.experts/mul",
    "fusion.15 fusion bf16[32768,2048]": FWD + "mlp/moe.combine/add",
    "fusion.16 fusion bf16[2,1,2048,7168]": RUN + "mlp/dot_general",
    "norm_fwd.17 custom-call tpu_custom_call bf16[8,4096,2048]":
        FWD + "mlp/norm_fwd/pallas_call",
}
SIZES = {
    "layer_pattern": "C-C-*eCeCeCe", "remat": "full", "d_model": 2048,
    "n_head": 32, "n_kv_head": 8, "head_dim": 64, "d_expert": 1792,
    "n_experts": 32, "n_experts_held": 8, "expert_top_k": 4,
}


def _spans(steps=3):
    spans = Spans()
    spans.spans = [("traced_window", 0.0, 10.0)] + [
        ("dispatch", 1.0 + i, 1.5 + i) for i in range(steps)
    ]
    return spans


def _run(op_names=OP_NAMES, by_name=BY_NAME, said=None, rows=None):
    first = {
        "busy_s": 2.0, "by_name": by_name, "modules": ["jit_step_fn"],
        "op_names": {
            k: {v: by_name[k][0]} for k, v in op_names.items() if k in by_name
        },
    }
    said = [] if said is None else said
    return {
        "trace": {"per_device": [first]},
        "say": lambda **record: said.append(record),
        "sizes": SIZES, "seq": 4096, "spans": _spans(),
        "window": {"steps": 45, "tokens": 32768, "seconds": 30.0},
        "peaks": peaks.chip_peaks("TPU v5 lite"),
        # two warm-up steps, then the three traced
        "step_metrics": {
            "moe_held_rows": rows or [36000.0, 35000.0, 33000.0, 32768.0, 32536.0]
        },
    }


def test_gated_conv_bytes_by_hand():
    # an array-pass at the cell's size: 8 x 4,096 x 2,048 bf16
    one = 8 * 4096 * 2048 * 2
    assert one == 134_217_728
    # a step: five layers, two forwards of 4 passes and a backward of 7
    assert lfm2.gated_conv_bytes(SIZES, 8, 4096, 1) == 5 * 15 * one
    no_remat = dict(SIZES, remat="none")
    assert lfm2.gated_conv_bytes(no_remat, 8, 4096, 3) == 3 * 5 * 11 * one
    p = peaks.chip_peaks("TPU v5 lite")
    assert round(4 * one / p.hbm_bytes_s * 1e3, 2) == 0.66
    assert round(7 * one / p.hbm_bytes_s * 1e3, 2) == 1.15


def test_gated_conv_roofline_is_the_operations_bytes_over_the_scopes_time():
    said = []
    got = _reader("lfm2.gated_conv_roofline")(_run(said=said))
    hbm = peaks.chip_peaks("TPU v5 lite").hbm_bytes_s
    want = 3 * 5 * 15 * 134_217_728 / hbm / (0.0096 + 0.0144 + 0.0225)
    assert got == pytest.approx(100 * want) and 0 < got < 100
    assert said[-1]["event"] == "gated_conv_rows" and len(said[-1]["rows"]) == 3
    # another body under the same scope, three times as slow: the same
    # bytes, a third of the share — the yardstick does not move with it
    slow = {
        k: ([3 * v[0], v[1]] if k.startswith("gated_conv") else v)
        for k, v in BY_NAME.items()
    }
    assert _reader("lfm2.gated_conv_roofline")(_run(by_name=slow)) == (
        pytest.approx(got / 3)
    )
    xla = {
        k.replace("gated_conv_fwd", "fusion.9").replace(
            "gated_conv_bwd", "fusion.8"
        ): v
        for k, v in BY_NAME.items()
    }
    names = {
        k.replace("gated_conv_fwd", "fusion.9").replace(
            "gated_conv_bwd", "fusion.8"
        ): v
        for k, v in OP_NAMES.items()
    }
    assert _reader("lfm2.gated_conv_roofline")(
        _run(op_names=names, by_name=xla)
    ) == pytest.approx(got)


def test_flash_and_grouped_matmul_rooflines_by_hand():
    said = []
    got = _reader("lfm2.flash_roofline")(_run(said=said))
    peak = peaks.chip_peaks("TPU v5 lite").bf16_flops
    pair = 2 * 64 * 32 * 8 * 4096 * 2048.5
    want = 3 * (2 + 3 + 4) * pair / peak / (0.034 + 0.052 + 0.051)
    assert got == pytest.approx(100 * want) and 0 < got < 100
    assert said[-1]["event"] == "flash_rows" and len(said[-1]["rows"]) == 3
    assert round(2 * pair / 1e12, 2) == 0.55
    got = _reader("lfm2.grouped_matmul_roofline")(_run(said=said))
    # the mean of the three traced steps' rows, not the warm-up's
    want = 144 * 2 * 32768 * 2048 * 1792 / peak / 0.363
    assert got == pytest.approx(100 * want) and 0 < got < 100
    assert said[-1]["received"] == 32768 and said[-1]["calls"] == 144


@pytest.mark.parametrize(
    "metric,want",
    [
        # of 2.0 busy seconds
        ("lfm2.conv_mixer_share", 0.0096 + 0.0144 + 0.0225 + 0.20 + 0.10),
        ("lfm2.gated_conv_share", 0.0096 + 0.0144 + 0.0225),
        ("lfm2.attn_share", 0.034 + 0.052 + 0.051 + 0.06),
        # the dense MLPs and the routed parts' norms; nothing of moe.*
        ("lfm2.dense_mlp_share", 0.40 + 0.004),
        ("lfm2.moe_share", 0.01 + 0.13 + 0.05 + 0.06 + 0.363),
    ],
)
def test_scope_share_readers(metric, want):
    said = []
    got = _reader(metric)(_run(said=said))
    assert got == pytest.approx(100 * want / 2.0) and got < 100
    assert said[0]["event"] == "scope_rows" and said[0]["metric"] == metric
    # a program without the scope (the parent of PR 73): nothing, and
    # nothing raised
    assert _reader(metric)(_run(op_names={})) is None


def test_readers_read_nothing_from_a_program_without_the_part():
    no_kernels = {
        k: v for k, v in BY_NAME.items()
        if not k.startswith(("gated_conv", "flash", "ragged"))
    }
    run = _run(op_names={}, by_name=no_kernels)
    for name in METRICS[:-1]:
        assert _reader(name)(run) is None, name


def test_held_rows_ratio_reads_the_programs_counter():
    assert _reader("lfm2.held_rows_ratio")(_run()) == 33000.0 / 32768
    run = _run()
    run["step_metrics"] = {}
    assert _reader("lfm2.held_rows_ratio")(run) is None


def test_readers_return_nothing_without_a_device_trace():
    run = dict(_run(), trace=None)
    for name in METRICS[:-1]:
        assert _reader(name)(run) is None, name


# ---- run.py end to end at a tiny size ---------------------------------------

TINY_SIZES = {
    "n_layer": 4, "layer_pattern": "C-C-*eCe", "d_model": 128, "n_head": 4,
    "n_kv_head": 2, "head_dim": 32, "qk_head_norm": True, "conv_kernel": 3,
    "rope_theta": 1000.0, "norm": "rmsnorm", "norm_eps": 1e-5,
    "act": "swiglu", "pos": "rope", "tie_embeddings": True,
    "vocab_size": 512, "max_seq": 128, "remat": "full", "d_ff": 256,
    "n_experts": 8, "n_experts_held": 4, "expert_offset": 0,
    "expert_top_k": 2, "d_expert": 64, "n_shared_experts": 0,
    "moe_impl": "ragged", "moe_score": "sigmoid", "moe_renorm_topk": True,
    "routed_scaling_factor": 1.0, "moe_aux_coef": 0.0, "moe_z_coef": 0.0,
}
TINY = {
    "source": "test",
    "program": {
        "model": "lfm2-8b-a1b",
        "overrides": {
            "n_layer": 4, "layer_pattern": "C-C-*eCe", "d_model": 128,
            "n_head": 4, "n_kv_head": 2, "d_ff": 256, "vocab_size": 512,
            "max_seq": 128, "rope_theta": 1000.0, "d_expert": 64,
            "n_experts": 8, "expert_top_k": 2, "n_experts_held": 4,
            "expert_offset": 0, "remat": "full", "attn_block_q": 128,
            "attn_block_k": 128, "param_dtype": "bfloat16",
        },
        "mesh": {"dp": -1},
        "comm": None,
        "optimizer": {"learning_rate": 1e-4, "warmup_steps": 2,
                      "decay_steps": 100},
    },
    "sizes": TINY_SIZES,
    "reference": "lfm2_moe_plain",
    "check": {"kind": "routed"},
}
CHECKS = [
    "choices_valid", "routing_regret", "logits_vs_reference",
    "logits_rms_vs_reference", "loss_vs_reference",
    "loss_vs_free_reference", "first_step_loss", "no_compile_in_window",
    "no_failed_step",
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
    assert len(ref["moved_by_layer"]) == 2
    assert ref["reference_terms"] == {}
    assert ref["forced_logit_err"] < 4e-2
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
    assert "lfm2.gated_conv_roofline" not in result["metrics"]
    assert 0 < result["metrics"]["lfm2.held_rows_ratio"]["value"] < 2


@pytest.mark.parametrize("defect", sorted(defects.PLANT))
def test_comparison_fails(monkeypatch, capsys, defect):
    """Sound, the tiny cell reads about 1e-2 on the logits in bf16; each
    defect has to push a check past the CHIP's limits (4e-2 at the
    maximum, 2.5e-2 rms), which are the ones ``run.py`` holds."""
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
