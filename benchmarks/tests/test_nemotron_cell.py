"""What PR 41 adds to the benchmark, on the CPU: the required FLOPs of
``references/nemotron_h_plain.py`` by hand, the committed file's
``sizes`` against the program's model with its overrides and against
the catalog's published keys, the three new readers on a recorded
``op_names`` table, ``run.py`` end to end at a tiny size of this
architecture, and the defects of ``nemotron_defects.py``, each of which
the comparison has to fail."""

import json
import os

import pytest

from benchmarks.lib import flops
from benchmarks.references import nemotron_h_plain as plain
from benchmarks.tests import nemotron_defects as defects
from benchmarks.tests import test_rehearsal as rehearsal
from benchmarks.tests.test_zero_readers import _reader

ROOT = rehearsal.ROOT
CELL = "nemotron3super-ep64-train-b1s8192"
CONFIG = "nemotron-3-super-ep64-1chip"


def _config():
    path = os.path.join(ROOT, "benchmarks", "configs", CONFIG + ".json")
    with open(path) as f:
        return json.load(f)


# ---- required FLOPs ---------------------------------------------------------
# per layer, by hand (the published widths): a Mamba-2 mixer 4096 x 18,560
# + 8192 x 4096 = 109,576,192 in its two matrices and 2 x 128 x 64 x 128 =
# 2,097,152 multiply-adds of the recurrence; the attention 2 x 4096^2 + 2 x
# 4096 x 256 = 35,651,584; a routed layer beside its experts: router 4096 x
# 512 + latent 2 x 4096 x 1024 + shared 2 x 4096 x 5376 = 54,525,952, and
# 22 x 8 / 512 experts of 2 x 1024 x 2688 = 5,505,024. The module: 2 x
# 4096^2 + an attention and a routed layer + the head (4096 x 16,384).


def test_required_terms_by_hand():
    sizes = _config()["sizes"]
    terms = plain.required_terms(sizes, 8192)
    mamba = 109_576_192 + 2_097_152
    routed = 54_525_952 + 22 * 8 / 512 * 5_505_024
    head = 67_108_864
    by_hand = (
        5 * mamba + 35_651_584 + 5 * routed
        + (33_554_432 + 35_651_584 + routed + head) + head
    )
    assert terms["multiplied_params"] == int(by_hand) == 1_135_951_872
    # two attention layers (the trunk's and the module's) of 32 x 128
    assert terms["attention_pair_channels"] == 2 * 4096 * 4096.5
    need = flops.resolve(_config(), 8192)
    assert need == 6.0 * int(by_hand) + 12.0 * 2 * 4096 * 4096.5
    assert round(need / 1e9, 2) == 7.22
    # the shares ISSUE 41 states: mixers 46%, routed blocks 28% (held
    # experts 0.9%), head 11%, attention pairs 5.6%, the recurrence 0.9%
    assert round(5 * 6 * mamba / need, 2) == 0.46
    assert round(6 * 6 * routed / need, 2) == 0.28
    assert round(6 * 6 * 22 * 8 / 512 * 5_505_024 / need, 3) == 0.009
    assert round(2 * 6 * head / need, 2) == 0.11
    assert round(12 * 2 * 4096 * 4096.5 / need, 3) == 0.056
    assert round(5 * 6 * 2_097_152 / need, 3) == 0.009


def test_required_terms_follow_the_pattern_and_the_share():
    sizes = _config()["sizes"]
    base = plain.required_terms(sizes, 8192)
    longer = plain.required_terms(
        dict(sizes, layer_pattern=sizes["layer_pattern"] + "M"), 8192
    )
    assert longer["multiplied_params"] - base["multiplied_params"] == (
        109_576_192 + 2_097_152
    )
    every = plain.required_terms(dict(sizes, n_experts_held=512), 8192)
    # all 512 held: 22 whole experts a token, in 5 layers and the module
    assert every["multiplied_params"] - base["multiplied_params"] == int(
        6 * (22 - 22 * 8 / 512) * 5_505_024
    )


# ---- the file against the program and the source ----------------------------


def test_sizes_are_the_programs_model_with_its_overrides():
    from benchmarks.runners.train import _program_config

    config = _config()
    cfg = _program_config(config)  # raises on a size the program lacks
    assert cfg.layer_pattern == "MEMEMEMEM*E" and cfg.mtp_pattern == "*E"
    assert cfg.train_only.startswith("state-space layers")
    assert cfg.num_params() == 1_378_721_664
    assert cfg.flops_per_token(8192) == flops.resolve(config, 8192)
    # the full model's published pattern holds this period at 27..37
    from dlrover_tpu.models import get_config

    full = get_config(config["program"]["model"])
    assert full.layer_pattern[27:38] == cfg.layer_pattern
    assert (full.n_layer, full.n_experts, full.vocab_size) == (
        88, 512, 131072
    )


def test_file_holds_the_published_keys_but_for_the_stated_cuts():
    config = _config()
    manifest = rehearsal._manifest()
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert sorted(entry["reduced"]) == sorted(config["reduced"])
    published = {
        "hidden_size": 4096, "mamba_num_heads": 128, "mamba_head_dim": 64,
        "ssm_state_size": 128, "n_groups": 8, "conv_kernel": 4,
        "chunk_size": 128, "num_attention_heads": 32,
        "num_key_value_heads": 2, "head_dim": 128, "num_experts_per_tok": 22,
        "moe_latent_size": 1024, "moe_intermediate_size": 2688,
        "moe_shared_expert_intermediate_size": 5376,
        "routed_scaling_factor": 5, "intermediate_size": 2688,
    }
    assert {k: config[k] for k in published} == published
    cut = {
        "num_hidden_layers": 11, "hybrid_override_pattern": "MEMEMEMEM*E",
        "n_routed_experts": 8, "vocab_size": 16384,
        "max_position_embeddings": 8192, "norm_eps": 1e-06,
        "layer_norm_epsilon": 1e-06,
    }
    assert {k: config[k] for k in cut} == cut
    sizes = config["sizes"]
    assert (sizes["n_experts"], sizes["n_experts_held"]) == (512, 8)
    # the gated group norm, this PR's code, keeps the published epsilon
    assert (sizes["norm_eps"], sizes["ssm_norm_eps"]) == (1e-06, 1e-05)
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "b1s8192", 1
    )


# ---- the scope readers ------------------------------------------------------
# rows as a traced step of the cell names them (op_names of the compiled
# text: forward, the layer's recomputation and the head block's own, and
# the backward)

STEP = "jit(step_fn)/jit(main)/"
BACK = STEP + "transpose(jvp(ssm))/"
BY_NAME = {
    "fusion.11 fusion bf16[1,8192,18560]": [0.30, 15],
    "fusion.12 fusion f32[1,8192,10240]": [0.05, 15],
    "fusion.13 fusion f32[1,64,1,16,128,128]": [0.20, 120],
    "fusion.14 fusion f32[1,64,1,16,64,128]": [0.10, 120],
    "fusion.15 fusion bf16[1,8192,8192]": [0.04, 15],
    "fusion.21 fusion bf16[8192,1024]": [0.06, 18],
    "fusion.22 fusion bf16[8192,4096]": [0.08, 18],
    "fusion.23 fusion bf16[8192,5376]": [0.40, 18],
    "ragged-dot-none.7 custom-call tpu_custom_call bf16[65536,2688]": [0.2, 36],
}
OP_NAMES = {
    "fusion.11 fusion bf16[1,8192,18560]":
        STEP + "jvp(ssm)/checkpoint/dot_general",
    "fusion.12 fusion f32[1,8192,10240]":
        STEP + "jvp(ssm)/checkpoint/ssm.conv/add",
    "fusion.13 fusion f32[1,64,1,16,128,128]":
        BACK + "checkpoint/ssm.scan/while/body/checkpoint/exp",
    "fusion.14 fusion f32[1,64,1,16,64,128]":
        STEP + "jvp(ssm)/checkpoint/ssm.scan/while/body/dot_general",
    "fusion.15 fusion bf16[1,8192,8192]":
        BACK + "checkpoint/mul",
    "fusion.21 fusion bf16[8192,1024]":
        STEP + "jvp(mlp)/checkpoint/moe.latent/dot_general",
    "fusion.22 fusion bf16[8192,4096]":
        STEP + "transpose(jvp(mlp))/checkpoint/moe.latent/dot_general",
    "fusion.23 fusion bf16[8192,5376]":
        STEP + "jvp(mlp)/checkpoint/moe.shared/dot_general",
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
        ("ssm.mixer_share", "ssm", 5, 0.69),
        ("ssm.scan_share", "ssm.scan", 2, 0.30),
        ("moe.latent_share", "moe.latent", 2, 0.14),
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
    for name in ("ssm.mixer_share", "ssm.scan_share", "moe.latent_share"):
        entry = next(m for m in manifest["per_layer"] if m["name"] == name)
        assert entry["workloads"] == [CELL]
        assert entry["moves"] == "train_tokens_per_s"
        assert entry["source"] == "device_trace" and entry["unit"] == "%"
    # what was there keeps its lists
    for name in ("mtp.share", "moe.held_rows_ratio", "mla.latent_share"):
        entry = next(m for m in manifest["per_layer"] if m["name"] == name)
        assert CELL not in entry["workloads"]


# ---- run.py end to end at a tiny size ---------------------------------------

_OVERRIDES = {
    "n_layer": 5, "layer_pattern": "MEM*E", "d_model": 128, "n_head": 4,
    "n_kv_head": 2, "d_head": 32, "vocab_size": 512, "max_seq": 128,
    "mamba_num_heads": 8, "mamba_head_dim": 16, "ssm_state_size": 16,
    "n_groups": 2, "ssm_chunk": 32, "ssm_head_block": 4, "n_experts": 16,
    "expert_top_k": 6, "d_expert": 64, "moe_latent_size": 64,
    "d_shared_expert": 192, "n_experts_held": 4, "expert_offset": 0,
    "remat": "full", "attn_block_q": 128, "attn_block_k": 128,
}
TINY = {
    "source": "test",
    "program": {
        "model": "nemotron-3-super",
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
        mtp_pattern="*E", norm="rmsnorm", norm_eps=1e-6, act="relu2",
        pos="none", tie_embeddings=False, conv_kernel=4, ssm_norm_eps=1e-5,
        n_shared_experts=1, moe_impl="ragged", moe_score="sigmoid",
        moe_renorm_topk=True, routed_scaling_factor=5.0, n_mtp_module=1,
        mtp_loss_coef=0.3,
    ),
    "reference": "nemotron_h_plain",
    "check": {"kind": "routed"},
}
CHECKS = [
    "choices_valid", "routing_regret", "logits_vs_reference",
    "logits_rms_vs_reference", "loss_vs_reference", "mtp_loss_vs_reference",
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
    # one row of choices a routed layer, the module's last
    assert len(ref["moved_by_layer"]) == 2 + 1
    assert set(ref["reference_terms"]) == {"mtp_loss"}
    assert set(ref["program_losses"]) == {"loss", "mtp_loss"}
    assert ref["forced_logit_err"] < 1e-4
    if not trace:
        assert set(result["metrics"]) == {"train_tokens_per_s", "setup_s"}
        return
    listed = {
        m["name"] for m in manifest["per_layer"]
        if "workloads" not in m or CELL in m["workloads"]
    }
    assert {"ssm.mixer_share", "ssm.scan_share", "moe.latent_share"} <= listed
    # no device plane on the CPU: the trace readers return nothing and
    # the line leaves them out
    assert set(result["metrics"]) <= listed
    assert "ssm.scan_share" not in result["metrics"]


@pytest.mark.parametrize("defect", sorted(defects.INJECT))
def test_comparison_fails(monkeypatch, capsys, defect):
    """Sound, the tiny cell reads 1e-6 on the logits; each defect has to
    push a check past the CHIP's limits (4e-2 at the maximum, 2.5e-2
    rms), which are the ones ``run.py`` holds."""
    _this_cell_first(monkeypatch)
    defects.INJECT[defect](monkeypatch.setattr)
    rc, _, _, lines = rehearsal._run_patched(
        monkeypatch, capsys, TINY, 0, seed=rehearsal.ROUTED_SEED
    )
    assert rc == 0
    checks, _ = rehearsal._events(lines)
    failed = {name for name, c in checks.items() if not c["ok"]}
    assert failed & set(defects.CAUGHT_BY[defect]), (defect, checks)
    assert json.loads(lines[-1])["correct"] is False
