"""What PR 47 adds to the benchmark, on the CPU: the required FLOPs of
``references/trinity_afmoe_plain.py`` by hand, the committed file's
``sizes`` against the program's model with its overrides and against
the catalog's published keys, the four new readers on a canned
``op_names`` table (a missing scope raises; the roofline share cannot
pass 100), and ``run.py`` end to end at a tiny size of this
architecture, sound and with one defect per new part."""

import json
import os

import pytest

from benchmarks.lib import flops, peaks
from benchmarks.references import trinity_afmoe_plain as plain
from benchmarks.tests import test_rehearsal as rehearsal
from benchmarks.tests import trinity_defects as defects
from benchmarks.tests.test_zero_readers import _reader

ROOT = rehearsal.ROOT
CELL = "trinitymini-ep8-train-b1s16384"
CONFIG = "trinity-mini-ep8-1chip"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def _config():
    path = os.path.join(ROOT, "benchmarks", "configs", CONFIG + ".json")
    with open(path) as f:
        return json.load(f)


# ---- required FLOPs ---------------------------------------------------------
# per layer, by hand (Trinity-Mini's widths): attention q, o and the gate
# 3 x 2048 x 4096 + k and v 2 x 2048 x 512 = 27,262,976; the dense MLP 3 x
# 2048 x 6144 = 37,748,736; one expert 3 x 2048 x 1024 = 6,291,456; the
# router 2048 x 128 = 262,144. A routed layer on a chip that holds 16 of
# 128 experts: attention + router + (8 x 16 / 128 + 1) experts = 40,108,032.
# The head 2048 x 25,024 = 51,249,152. Pairs a query: a window layer
# mean_span(16384, 2048) = 1,920.0625, a full layer 8,192.5.


@pytest.mark.parametrize(
    "depth,kinds,multiplied,pairs",
    [
        # the depth the cell runs: 1 dense + one period
        (5, "SSSSF", 276_692_992, 4 * 1920.0625 + 8192.5),
        # ISSUE 47's first choice, which does not fit the chip
        (9, "SSSSFSSSF", 437_125_120, 7 * 1920.0625 + 2 * 8192.5),
    ],
)
def test_required_terms_by_hand(depth, kinds, multiplied, pairs):
    sizes = dict(_config()["sizes"], n_layer=depth, layer_types=kinds)
    terms = plain.required_terms(sizes, 16384)
    by_hand = (
        (27_262_976 + 37_748_736) + (depth - 1) * 40_108_032 + 51_249_152
    )
    assert terms["multiplied_params"] == by_hand == multiplied
    assert terms["attention_pair_channels"] == 4096 * pairs
    if depth == 9:
        assert round(pairs) == 29_825
        return
    assert _config()["sizes"]["layer_types"] == kinds
    need = flops.resolve(_config(), 16384)
    assert need == 6.0 * by_hand + 12.0 * 4096 * pairs
    assert round(need / 1e9, 3) == 2.44
    # attention's pairs are 32% of it, the one full layer 52% of those
    assert round(12.0 * 4096 * pairs / need, 2) == 0.32
    assert round(8192.5 / pairs, 2) == 0.52


def test_required_terms_follow_the_kinds_and_the_share():
    sizes = _config()["sizes"]
    base = plain.required_terms(sizes, 16384)
    full = plain.required_terms(dict(sizes, layer_types="SSSFF"), 16384)
    assert full["multiplied_params"] == base["multiplied_params"]
    assert full["attention_pair_channels"] - base[
        "attention_pair_channels"
    ] == 4096 * (8192.5 - 1920.0625)
    # at 2,048 tokens both kinds are the same causal attention
    short = plain.required_terms(sizes, 2048)
    assert short["attention_pair_channels"] == 5 * 4096 * 1024.5
    every = plain.required_terms(dict(sizes, n_experts_held=128), 16384)
    # all 128 held: eight whole experts a token, in 4 layers
    assert every["multiplied_params"] - base["multiplied_params"] == (
        4 * (8 - 1) * 6_291_456
    )
    with pytest.raises(ValueError, match="names not"):
        plain.required_terms(dict(sizes, n_layer=9), 16384)


# ---- the file against the program and the source ----------------------------


def test_sizes_are_the_programs_model_with_its_overrides():
    from benchmarks.runners.train import _program_config
    from dlrover_tpu.models import get_config

    config = _config()
    cfg = _program_config(config)  # raises on a size the program lacks
    assert cfg.layer_types == "SSSSF" and cfg.n_dense_layer == 1
    assert cfg.train_only == "a trunk whose layers differ"
    # ``norm_eps`` is the one size the runner does not hold the program
    # to: held here
    assert cfg.norm_eps == config["sizes"]["norm_eps"] == 1e-5
    assert cfg.flops_per_token(16384) == flops.resolve(config, 16384)
    # the full model's published kinds hold this period at 4..7 behind
    # a leading window layer
    full = get_config(config["program"]["model"])
    assert full.layer_types[0] + full.layer_types[4:8] == cfg.layer_types
    assert (full.n_layer, full.n_dense_layer, full.n_experts,
            full.vocab_size) == (32, 2, 128, 200192)
    assert config["reference"] == "trinity_afmoe_plain"
    assert config["check"] == {"kind": "routed"}


def test_file_holds_the_catalog_row_but_for_the_stated_cuts():
    config = _config()
    manifest = rehearsal._manifest()
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert sorted(entry["reduced"]) == sorted(config["reduced"])
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(CATALOG) as f:
        row = next(
            r for r in map(json.loads, f) if r["name"] == "Trinity-Mini"
        )
    assert entry["source"] == config["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items() if config.get(k) != v}
    assert differs == set(entry["reduced"])
    cut = {
        "num_hidden_layers": 5, "num_dense_layers": 1, "num_experts": 16,
        "vocab_size": 25024, "max_position_embeddings": 16384,
    }
    assert {k: config[k] for k in cut} == cut
    # no width among the cuts, and the program's sizes are the published
    widths = {
        "d_model": "hidden_size", "n_head": "num_attention_heads",
        "n_kv_head": "num_key_value_heads", "head_dim": "head_dim",
        "d_ff": "intermediate_size", "d_expert": "moe_intermediate_size",
        "expert_top_k": "num_experts_per_tok", "attn_window": "sliding_window",
        "rope_theta": "rope_theta", "norm_eps": "rms_norm_eps",
        "routed_scaling_factor": "route_scale",
        "moe_aux_coef": "load_balance_coeff",
        "n_shared_experts": "num_shared_experts",
    }
    for ours, theirs in widths.items():
        assert config["sizes"][ours] == row["config"][theirs], ours
    assert config["sizes"]["n_experts"] == row["config"]["num_experts"]
    # the period: three sliding layers, then a full one
    kinds = "".join(
        {"sliding_attention": "S", "full_attention": "F"}[k]
        for k in config["layer_types"]
    )
    assert kinds == "SSSF" * 8
    # every item the published config has no key for is under ``assumed``
    assert {
        "output_gate", "qk_norm", "no_positions_on_full_layers",
        "post_norms", "embedding_scale", "rope_pairing", "softmax_scale",
        "router_losses", "selection_bias", "weights", "optimizer",
    } <= set(config["assumed"])


def test_manifest_lists_the_cell_and_its_four_metrics():
    manifest = rehearsal._manifest()
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "b1s16384", 1
    )
    with open(os.path.join(ROOT, "benchmarks", "traffic", "b1s16384.json")) as f:
        traffic = json.load(f)
    assert (traffic["runner"], traffic["global_batch"], traffic["seq"]) == (
        "train", 1, 16384
    )
    assert (traffic["warmup_steps"], traffic["trace_steps"]) == (2, 3)
    assert traffic["check"] == {"q_block": 512}
    ours = [m for m in manifest["per_layer"] if m["name"].startswith("swa.")]
    assert [m["name"] for m in ours] == [
        "swa.window_share", "swa.full_share", "swa.gate_share",
        "swa.flash_roofline",
    ]
    # found by name: later cells append their metrics behind these
    names = [m["name"] for m in manifest["per_layer"]]
    assert all(names.count(m["name"]) == 1 for m in ours)
    for m in ours:
        assert m["workloads"] == [CELL] and m["unit"] == "%"
        assert m["moves"] == "train_tokens_per_s"
        assert m["layer"] == "attention by layer kind"
        assert m["source"] == "device_trace"


# ---- the readers ------------------------------------------------------------

W = "jit(step_fn)/jvp()/checkpoint/attn/attn.window/"
F = "jit(step_fn)/jvp()/while/body/checkpoint/attn/attn.full/"
BACK = "jit(step_fn)/transpose(jvp())/while/body/checkpoint/"
FLASH = " custom-call tpu_custom_call bf16[32,16384,128]"
BY_NAME = {
    # three window layers in the scanned period and the dense prefix's:
    # forward and recomputed; one full layer: forward only
    "flash_fwd.1" + FLASH: [0.096, 24],
    "flash_bwd_dq.2" + FLASH: [0.072, 12],
    "flash_bwd_dkv.3" + FLASH: [0.084, 12],
    "flash_fwd.4" + FLASH: [0.036, 3],
    "flash_bwd_dq.5" + FLASH: [0.054, 3],
    "flash_bwd_dkv.6" + FLASH: [0.060, 3],
    "fusion.7 fusion bf16[1,16384,4096]": [0.04, 30],
    "fusion.8 fusion bf16[16384,2048]": [0.02, 15],
    "ragged-dot-none.9 custom-call tpu_custom_call bf16[131072,1024]": [0.2, 36],
}
OP_NAMES = {
    "flash_fwd.1" + FLASH: W + "flash_fwd/pallas_call",
    "flash_bwd_dq.2" + FLASH:
        BACK + "attn/attn.window/flash_bwd_dq/pallas_call",
    "flash_bwd_dkv.3" + FLASH:
        BACK + "attn/attn.window/flash_bwd_dkv/pallas_call",
    "flash_fwd.4" + FLASH: F + "flash_fwd/pallas_call",
    "flash_bwd_dq.5" + FLASH: BACK + "attn/attn.full/flash_bwd_dq/pallas_call",
    "flash_bwd_dkv.6" + FLASH:
        BACK + "attn/attn.full/flash_bwd_dkv/pallas_call",
    "fusion.7 fusion bf16[1,16384,4096]": W + "attn.gate/mul",
    "fusion.8 fusion bf16[16384,2048]": F + "attn.gate/dot_general",
}
SIZES = {"n_head": 32, "n_kv_head": 4, "head_dim": 128, "attn_window": 2048}


def _run(op_names=OP_NAMES, by_name=BY_NAME, said=None):
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
        "sizes": SIZES, "seq": 16384,
        "window": {"steps": 30, "tokens": 16384, "seconds": 30.0},
        "peaks": peaks.chip_peaks("TPU v5 lite"),
    }


def _load(name):
    return _reader(name).__globals__


def test_flash_products_by_hand():
    call_flops = _load("swa.flash_roofline")["call_flops"]
    # one head, one sequence of 4, a window of 2: 1 + 2 + 2 + 2 = 7
    # useful pairs, 2 x 128 operations a pair and product
    assert call_flops(1, 1, 4, 1, 128, 2) == 7 * 2 * 128
    assert call_flops(1, 1, 4, 1, 128) == 10 * 2 * 128
    # the cell's forward calls: 2 products, 32 heads x 16384 queries
    window = call_flops(2, 1, 16384, 32, 128, 2048)
    full = call_flops(2, 1, 16384, 32, 128)
    assert window == 2 * 32 * 16384 * 1920.0625 * 256
    assert full == 2 * 32 * 16384 * 8192.5 * 256
    assert round(window / 1e12, 3) == 0.515 and round(full / 1e12, 3) == 2.199
    # compute bound in both kinds: 302 MB at 819 GB/s is 0.37 ms
    call_bytes = _load("swa.flash_roofline")["call_bytes"]
    assert call_bytes(1, 16384, 32, 4, 128) == 301_989_888
    assert window / 197e12 > 5 * call_bytes(1, 16384, 32, 4, 128) / 819e9


def test_flash_roofline_counts_each_call_by_the_kind_of_its_layer():
    read = _reader("swa.flash_roofline")
    unit = 32 * 16384 * 256.0  # one product over one key a query
    want = (
        (24 * 2 + 12 * 3 + 12 * 4) * 1920.0625 * unit
        + (3 * 2 + 3 * 3 + 3 * 4) * 8192.5 * unit
    ) / 0.402 / 197e12
    assert read(_run()) == pytest.approx(100.0 * want)
    assert read({"trace": None}) is None
    # a flash call under neither kind's scope: an error, not a guess
    no_kind = dict(OP_NAMES)
    no_kind["flash_fwd.4" + FLASH] = "jit(step_fn)/jvp()/attn/flash_fwd"
    with pytest.raises(LookupError, match="neither"):
        read(_run(op_names=no_kind))
    # a step without the kernels
    no_flash = {k: v for k, v in BY_NAME.items() if not k.startswith("flash")}
    with pytest.raises(LookupError, match="no flash"):
        read(_run(by_name=no_flash))


def test_flash_roofline_cannot_pass_100():
    """Kernels that ran AT the peak over the blocks they have to touch:
    the useful pairs are fewer than the executed ones in both kinds, so
    the share stays under 100."""
    read = _reader("swa.flash_roofline")
    call_flops = _load("swa.flash_roofline")["call_flops"]
    block = 1024
    # executed keys a query: a full layer's causal blocks, a window
    # layer's window rounded out to whole blocks
    executed = {
        2048: 16384 * 32 * (2048 + block) * 256.0,
        0: 32 * 256.0 * sum(
            (q // block + 1) * block for q in range(0, 16384, block)
        ) * block,
    }
    by_name = {}
    for label, (_s, calls) in BY_NAME.items():
        if not label.startswith("flash"):
            continue
        kernel = label.split(".")[0]
        products = dict(_load("swa.flash_roofline")["PRODUCTS"])[kernel]
        window = 2048 if "attn.window" in OP_NAMES[label] else 0
        assert call_flops(products, 1, 16384, 32, 128, window) < (
            products * executed[window]
        )
        by_name[label] = [
            calls * products * executed[window] / 197e12, calls
        ]
    value = read(_run(by_name=by_name))
    assert 60 < value < 100


@pytest.mark.parametrize(
    "metric,scope,rows,seconds",
    [
        ("swa.window_share", "attn.window", 4, 0.096 + 0.072 + 0.084 + 0.04),
        ("swa.full_share", "attn.full", 4, 0.036 + 0.054 + 0.060 + 0.02),
        ("swa.gate_share", "attn.gate", 2, 0.04 + 0.02),
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
    gone = {k: v for k, v in OP_NAMES.items() if scope not in v}
    with pytest.raises(LookupError, match=scope):
        read(_run(op_names=gone))


# ---- run.py end to end at a tiny size ---------------------------------------

TINY_SIZES = {
    "n_layer": 5, "n_dense_layer": 1, "layer_types": "SSSSF",
    "d_model": 128, "n_head": 4, "n_kv_head": 2, "head_dim": 32,
    "qk_head_norm": True, "attn_gate": True, "post_norm": True,
    "scale_embedding": True, "attn_window": 32, "rope_theta": 10000.0,
    "norm": "rmsnorm", "norm_eps": 1e-5, "act": "swiglu", "pos": "rope",
    "tie_embeddings": False, "vocab_size": 512, "max_seq": 128,
    "d_ff": 256, "n_experts": 8, "n_experts_held": 4, "expert_offset": 0,
    "expert_top_k": 2, "d_expert": 64, "n_shared_experts": 1,
    "moe_impl": "ragged", "moe_score": "sigmoid", "moe_renorm_topk": True,
    "routed_scaling_factor": 2.826, "moe_aux_coef": 0.001,
    "moe_z_coef": 0.0,
}
TINY = {
    "source": "test",
    "program": {
        "model": "trinity-mini",
        "overrides": {
            "n_layer": 5, "n_dense_layer": 1, "layer_types": "SSSSF",
            "d_model": 128, "n_head": 4, "n_kv_head": 2, "d_head": 32,
            "d_ff": 256, "vocab_size": 512, "max_seq": 128,
            "attn_window": 32, "d_expert": 64, "n_experts": 8,
            "expert_top_k": 2, "n_experts_held": 4, "expert_offset": 0,
            "remat": "full", "attn_block_q": 128, "attn_block_k": 128,
            "param_dtype": "bfloat16",
        },
        "mesh": {"dp": -1},
        "comm": None,
        "optimizer": {"learning_rate": 1e-4, "warmup_steps": 2,
                      "decay_steps": 100},
    },
    "sizes": TINY_SIZES,
    "reference": "trinity_afmoe_plain",
    "check": {"kind": "routed"},
}
CHECKS = [
    "choices_valid", "routing_regret", "logits_vs_reference",
    "logits_rms_vs_reference", "loss_vs_reference",
    "moe_lb_loss_vs_reference", "loss_vs_free_reference", "first_step_loss",
    "no_compile_in_window", "no_failed_step",
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
    # one row of choices a routed layer; the objective's one other term
    # reported by both sides
    assert len(ref["moved_by_layer"]) == 4
    assert set(ref["reference_terms"]) == {"moe_lb_loss"}
    assert set(ref["program_losses"]) == {"loss", "moe_lb_loss"}
    assert ref["forced_logit_err"] < 4e-2
    if not trace:
        assert set(result["metrics"]) == {"train_tokens_per_s", "setup_s"}
        return
    listed = {
        m["name"] for m in manifest["per_layer"]
        if "workloads" not in m or CELL in m["workloads"]
    }
    assert {"swa.window_share", "swa.full_share", "swa.gate_share",
            "swa.flash_roofline"} <= listed
    # no device plane on the CPU: the trace readers return nothing and
    # the line leaves them out
    assert set(result["metrics"]) <= listed
    assert "swa.flash_roofline" not in result["metrics"]


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
