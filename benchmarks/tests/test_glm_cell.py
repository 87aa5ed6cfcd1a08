"""What PR 34 adds to the benchmark, on the CPU: the required FLOPs of
``references/glm_moe_lite_plain.py`` by hand (the cut of ISSUE 33's
sketch and the committed file's own number), the products
``mla.flash_roofline`` counts by hand, the new readers on a hand-built
run, and ``run.py`` end to end at a tiny size of this architecture."""

import json
import os

import pytest

from benchmarks.lib import flops, peaks
from benchmarks.references import glm_moe_lite_plain as plain
from benchmarks.tests import test_rehearsal as rehearsal
from benchmarks.tests.test_zero_readers import _reader

ROOT = rehearsal.ROOT
CELL = "glm47flash-ep8-train-b2s8192"


def _config():
    path = os.path.join(
        ROOT, "benchmarks", "configs", "glm-4.7-flash-ep8-1chip.json"
    )
    with open(path) as f:
        return json.load(f)


# ---- required FLOPs ---------------------------------------------------------
# per layer, by hand (GLM-4.7-Flash's widths): attention 2048 x 768 + 768 x
# 20 x 256 + 2048 x 576 + 512 x 20 x 448 + 5120 x 2048 = 21,757,952; the
# dense MLP 3 x 2048 x 10240 = 62,914,560; one expert 3 x 2048 x 1536 =
# 9,437,184; the router 2048 x 64 = 131,072. A routed layer on a chip that
# holds 8 of 64 experts: attention + router + (4 x 8 / 64 + 1) experts =
# 36,044,800. The module: 2 x 2048^2 + a routed layer + the head
# (2048 x 19,360 = 39,649,280).


def test_required_terms_by_hand():
    sizes = _config()["sizes"]
    assert (sizes["n_layer"], sizes["n_dense_layer"]) == (9, 1)
    terms = plain.required_terms(sizes, 8192)
    by_hand = (
        (21_757_952 + 62_914_560) + 8 * 36_044_800
        + (8_388_608 + 36_044_800 + 39_649_280) + 39_649_280
    )
    assert by_hand == 496_762_880
    assert terms["multiplied_params"] == by_hand
    # ten attention layers of 20 heads x (256 + 256) / 2 channels
    assert terms["attention_pair_channels"] == 10 * 5120 * 4096.5
    need = flops.resolve(_config(), 8192)
    assert need == 6.0 * by_hand + 12.0 * 10 * 5120 * 4096.5
    assert round(need / 1e9, 3) == 5.497
    # latent attention is 70% of it, the held routed experts 4.6%
    attention = 10 * (6 * 21_757_952 + 12 * 5120 * 4096.5)
    held = 9 * 6 * 0.5 * 9_437_184
    assert round(attention / need, 2) == 0.70
    assert round(held / need, 3) == 0.046


def test_required_terms_follow_the_depth_and_the_share():
    sizes = _config()["sizes"]
    base = plain.required_terms(sizes, 8192)
    deeper = plain.required_terms(dict(sizes, n_layer=11), 8192)
    assert deeper["multiplied_params"] - base["multiplied_params"] == (
        2 * 36_044_800
    )
    every = plain.required_terms(dict(sizes, n_experts_held=64), 8192)
    # all 64 held: four whole experts a token, in 8 layers and the module
    assert every["multiplied_params"] - base["multiplied_params"] == (
        9 * (4 - 0.5) * 9_437_184
    )


# ---- mla.flash_roofline -----------------------------------------------------


def _load(name):
    return _reader(name).__globals__


def test_flash_products_by_hand():
    call_flops = _load("mla.flash_roofline")["call_flops"]
    # one head, one sequence of 4: 1 + 2 + 3 + 4 = 10 useful pairs, 2 x
    # 256 operations a pair and product
    assert call_flops(1, 1, 4, 1, 256) == 10 * 2 * 256
    # the cell's forward call: 2 products, 2 sequences x 20 heads x
    # 8192 x 4096.5 pairs, 512 operations each
    fwd = call_flops(2, 2, 8192, 20, 256)
    assert fwd == 2 * 2 * 20 * 8192 * 4096.5 * 512
    assert round(fwd / 1e12, 3) == 1.375
    assert call_flops(3, 2, 8192, 20, 256) == 1.5 * fwd
    assert call_flops(4, 2, 8192, 20, 256) == 2 * fwd
    # compute bound: 671 MB at 819 GB/s is 0.82 ms, the products 6.98
    call_bytes = _load("mla.flash_roofline")["call_bytes"]
    assert call_bytes(4, 2, 8192, 20, 256) == 671_088_640
    assert fwd / 197e12 > 8 * call_bytes(4, 2, 8192, 20, 256) / 819e9


BY_NAME = {
    "flash_fwd.3 custom-call tpu_custom_call bf16[40,8192,256]": [0.4, 60],
    "flash_bwd_dq.9 custom-call tpu_custom_call bf16[40,8192,256]": [0.3, 30],
    "flash_bwd_dkv.7 custom-call tpu_custom_call bf16[40,8192,256]": [0.4, 30],
    "fusion.5 fusion bf16[2,8192,20,256]": [0.05, 90],
    "fusion.9 fusion bf16[2,8192,2048]": [0.03, 30],
    "ragged-dot-none.7 custom-call tpu_custom_call bf16[65536,1536]": [0.2, 36],
}
STEP = "jit(step_fn)/transpose(jvp())/while/body/closed_call/checkpoint/"
OP_NAMES = {
    "fusion.5 fusion bf16[2,8192,20,256]":
        STEP + "attn/attn.latent/concatenate",
    "fusion.9 fusion bf16[2,8192,2048]":
        "jit(step_fn)/jvp(mtp)/checkpoint/attn/attn.latent/dot_general",
    "flash_fwd.3 custom-call tpu_custom_call bf16[40,8192,256]":
        STEP + "attn/flash_fwd/pallas_call",
}
SIZES = {
    "n_head": 20, "qk_nope_head_dim": 192, "qk_rope_head_dim": 64,
    "expert_top_k": 4, "n_experts_held": 8, "n_experts": 64,
}


def _run(op_names=OP_NAMES, said=None, **more):
    first = {
        "busy_s": 2.0, "by_name": BY_NAME, "modules": ["jit_step_fn"],
        "op_names": {k: {v: BY_NAME[k][0]} for k, v in op_names.items()},
    }
    said = [] if said is None else said
    return dict({
        "trace": {"per_device": [first]},
        "say": lambda **record: said.append(record),
        "sizes": SIZES, "seq": 8192,
        "window": {"steps": 20, "tokens": 16384, "seconds": 30.0},
        "peaks": peaks.chip_peaks("TPU v5 lite"),
    }, **more)


def test_flash_roofline_divides_the_products_by_the_kernels_time():
    read = _reader("mla.flash_roofline")
    fwd = 2 * 2 * 20 * 8192 * 4096.5 * 512
    want = (60 * fwd + 30 * 1.5 * fwd + 30 * 2 * fwd) / 1.1 / 197e12
    assert read(_run()) == pytest.approx(100.0 * want)
    assert read({"trace": None}) is None
    # a step without the kernels: nothing to read
    no_flash = {k: v for k, v in BY_NAME.items() if not k.startswith("flash")}
    run = _run()
    run["trace"]["per_device"][0]["by_name"] = no_flash
    assert read(run) is None


# ---- the scope readers ------------------------------------------------------


@pytest.mark.parametrize(
    "metric,scope,seconds",
    [("mla.latent_share", "attn.latent", 0.08), ("mtp.share", "mtp", 0.03)],
)
def test_scope_share_readers(metric, scope, seconds):
    read, said = _reader(metric), []
    assert read(_run(said=said)) == pytest.approx(100.0 * seconds / 2.0)
    rows = 2 if scope == "attn.latent" else 1
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


def test_held_rows_ratio():
    read = _reader("moe.held_rows_ratio")
    # balanced: 16384 tokens x 4 choices x 8 / 64 = 8192 rows
    assert read(_run(step_metrics={"moe_held_rows": [8192.0] * 5})) == 1.0
    run = _run(step_metrics={"moe_held_rows": [9000.0, 12288.0, 12288.0]})
    assert read(run) == 1.5
    # a program without the step metric (every expert held): left out
    assert read(_run(step_metrics={"loss": [1.0]})) is None


# ---- run.py end to end at a tiny size ---------------------------------------

TINY_GLM = {
    "source": "test",
    "program": {
        "model": "glm-4.7-flash",
        "overrides": {
            "n_layer": 3, "d_model": 128, "n_head": 2, "n_kv_head": 2,
            "d_ff": 256, "vocab_size": 512, "max_seq": 128,
            "q_lora_rank": 64, "kv_lora_rank": 32, "qk_nope_head_dim": 48,
            "qk_rope_head_dim": 16, "v_head_dim": 64, "d_expert": 64,
            "n_experts": 8, "expert_top_k": 2, "n_experts_held": 4,
            "expert_offset": 0, "remat": "full",
            "attn_block_q": 128, "attn_block_k": 128,
            "param_dtype": "bfloat16",
        },
        "mesh": {"dp": -1},
        "comm": None,
        "optimizer": {"learning_rate": 1e-4, "warmup_steps": 2,
                      "decay_steps": 100},
    },
    "sizes": {
        "n_layer": 3, "n_dense_layer": 1, "d_model": 128, "n_head": 2,
        "n_kv_head": 2, "d_ff": 256, "vocab_size": 512, "max_seq": 128,
        "norm": "rmsnorm", "norm_eps": 1e-6, "act": "swiglu", "pos": "rope",
        "tie_embeddings": False, "attn_window": 0, "rope_theta": 1000000.0,
        "q_lora_rank": 64, "kv_lora_rank": 32, "qk_nope_head_dim": 48,
        "qk_rope_head_dim": 16, "v_head_dim": 64, "n_experts": 8,
        "n_experts_held": 4, "expert_offset": 0, "expert_top_k": 2,
        "d_expert": 64, "n_shared_experts": 1, "moe_impl": "ragged",
        "moe_score": "sigmoid", "moe_renorm_topk": True,
        "routed_scaling_factor": 1.8, "n_mtp_module": 1,
        "mtp_loss_coef": 0.3,
    },
    "reference": "glm_moe_lite_plain",
    "check": {"kind": "routed"},
}
GLM_CHECKS = [
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
        monkeypatch, capsys, TINY_GLM, trace, seed=rehearsal.ROUTED_SEED
    )
    assert rc == 0 and cell["name"] == CELL
    result = json.loads(lines[-1])
    checks, events = rehearsal._events(lines)
    assert list(checks) == GLM_CHECKS
    assert all(c["ok"] for c in checks.values()), checks
    assert result["correct"] is True and result["failed"] == 0
    ref = events["reference"]
    # one row of choices a routed block, the module's last; the
    # objective's one other term reported by both sides
    assert len(ref["moved_by_layer"]) == 2 + 1
    assert set(ref["reference_terms"]) == {"mtp_loss"}
    assert set(ref["program_losses"]) == {"loss", "mtp_loss"}
    if not trace:
        assert set(result["metrics"]) == {"train_tokens_per_s", "setup_s"}
        return
    listed = {
        m["name"] for m in manifest["per_layer"]
        if "workloads" not in m or CELL in m["workloads"]
    }
    assert {"mla.latent_share", "mla.flash_roofline", "mtp.share",
            "moe.held_rows_ratio"} <= listed
    # no device plane on the CPU: the trace readers return nothing and
    # the line leaves them out; the step metric's reader reads
    assert set(result["metrics"]) <= listed
    assert result["metrics"]["moe.held_rows_ratio"]["value"] > 0
    assert "mla.flash_roofline" not in result["metrics"]
