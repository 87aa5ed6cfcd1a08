"""The MiniCPM-SALA cell (``minicpm-sala-l4-train-b1s16384``): the
manifest's new entries found by name, the reference's count of required
operations against hand arithmetic, the configuration file against the
program's model with its overrides, against ``decoder.init``'s shapes
and against the catalog's published keys, the five new readers on a
recorded ``op_names`` table, ``run.py`` end to end at a tiny size of
this architecture, and the defects of ``sala_defects.py``, each of which
a NAMED check of the ``selected`` comparison has to fail."""

import json
import os

import pytest

from benchmarks.lib import flops
from benchmarks.references import minicpm_sala_plain as plain
from benchmarks.tests import sala_defects as defects
from benchmarks.tests import test_rehearsal as rehearsal
from benchmarks.tests.test_zero_readers import _reader

ROOT = rehearsal.ROOT
CELL = "minicpm-sala-l4-train-b1s16384"
CONFIG = "minicpm-sala-l4"
SOURCE = "https://huggingface.co/openbmb/MiniCPM-SALA/blob/main/config.json"
READERS = {
    "sala.select_share": "block-sparse attention",
    "sala.sparse_attn_share": "block-sparse attention",
    "sala.linear_share": "linear attention",
    "sala.scan_share": "linear attention",
    "sala.flash_roofline": "block-sparse attention",
}


def _config():
    path = os.path.join(ROOT, "benchmarks", "configs", CONFIG + ".json")
    with open(path) as f:
        return json.load(f)


def _named(entries, name):
    (entry,) = [e for e in entries if e["name"] == name]
    return entry


# ---- the manifest, by name --------------------------------------------------


def test_manifest_holds_the_configuration_the_cell_and_five_metrics():
    manifest = rehearsal._manifest()
    entry = _named(manifest["configs"], CONFIG)
    assert entry["source"] == SOURCE
    assert entry["file"] == f"benchmarks/configs/{CONFIG}.json"
    assert sorted(entry["reduced"]) == [
        "max_position_embeddings", "mixer_types", "num_hidden_layers",
        "vocab_size",
    ]
    cell = _named(manifest["workloads"], CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "b1s16384", 1
    )
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200
    for name, layer in READERS.items():
        metric = _named(manifest["per_layer"], name)
        assert metric["workloads"] == [CELL]
        assert metric["moves"] == "train_tokens_per_s"
        assert metric["source"] == "device_trace" and metric["unit"] == "%"
        assert metric["layer"] == layer
        assert metric["better"] == (
            "higher" if name.endswith("_roofline") else "lower"
        )
        assert os.path.exists(
            os.path.join(ROOT, "benchmarks", "layer_metrics", name + ".py")
        )
    # what was there keeps its lists: no other metric names this cell
    for metric in manifest["per_layer"]:
        if metric["name"] not in READERS:
            assert CELL not in metric.get("workloads", ())
    # and the cell's traffic file is Trinity's, as it was
    with open(os.path.join(ROOT, "benchmarks", "traffic", "b1s16384.json")) as f:
        traffic = json.load(f)
    assert (traffic["global_batch"], traffic["seq"]) == (1, 16384)
    assert traffic["check"] == {"q_block": 512}


# ---- required FLOPs ---------------------------------------------------------
# per layer, by hand (the published widths). The sparse mixer: q, o and
# the gate 4096 x 4096 each, k and v 4096 x 256 each = 52,428,800. The
# lightning mixer: five matrices of 4096 x 4096 = 83,886,080, and the
# recurrence's update and read-out 2 x 4096 x 128 = 1,048,576. An MLP 3 x
# 4096 x 16,384 = 201,326,592. The head 4096 x 9,181 = 37,605,376.

SPARSE, LIGHTNING, MLP, HEAD = 52_428_800, 83_886_080, 201_326_592, 37_605_376
RECURRENCE = 2 * 4096 * 128


def test_required_terms_by_hand():
    sizes = _config()["sizes"]
    terms = plain.required_terms(sizes, 16384)
    by_hand = SPARSE + 3 * (LIGHTNING + RECURRENCE) + 4 * MLP + HEAD
    assert terms["multiplied_params"] == by_hand == 1_150_144_512
    # 64 blocks of 64 keys a query: 3,560.5 keys; the pooled scorer half
    # a pair-channel over 8,192.5 / 16 pooled keys a query
    assert flops.mean_span(16384, topk=64, block=64) == 3560.5
    pairs = 4096 * 3560.5 + 4096 / 2 * 8192.5 / 16
    assert terms["attention_pair_channels"] == pairs
    need = flops.resolve(_config(), 16384)
    assert need == 6.0 * by_hand + 12.0 * pairs
    assert round(need * 16384 / 1e12, 1) == 116.1  # TFLOP a step
    # the shares: MLPs 68.2%, the mixers' matrices 25.7% (lightning
    # 21.3%), the head 3.2%, the selected pairs 2.5%, the scorer 0.2%,
    # the recurrence 0.3%
    assert round(4 * 6 * MLP / need, 3) == 0.682
    assert round(6 * (SPARSE + 3 * LIGHTNING) / need, 3) == 0.257
    assert round(6 * HEAD / need, 3) == 0.032
    assert round(12 * 4096 * 3560.5 / need, 3) == 0.025
    assert round(12 * 4096 / 2 * 8192.5 / 16 / need, 4) == 0.0018
    assert round(3 * 6 * RECURRENCE / need, 4) == 0.0027
    # a sequence the model runs dense counts every visible key, no scorer
    dense = plain.required_terms(sizes, 8192)
    assert dense["attention_pair_channels"] == 4096 * 4096.5


def test_required_terms_follow_the_layers():
    sizes = _config()["sizes"]
    base = plain.required_terms(sizes, 16384)
    longer = plain.required_terms(
        dict(sizes, n_layer=5, layer_pattern="S-L-L-L-S-"), 16384
    )
    assert longer["multiplied_params"] - base["multiplied_params"] == (
        SPARSE + MLP
    )
    assert longer["attention_pair_channels"] == (
        2 * base["attention_pair_channels"]
    )
    with pytest.raises(ValueError, match="a mixer"):
        plain.required_terms(dict(sizes, layer_pattern="S-L-L-L"), 16384)
    with pytest.raises(ValueError, match="a mixer"):
        plain.required_terms(dict(sizes, layer_pattern="*-L-L-L-"), 16384)


# ---- the file against the program and the source ----------------------------


def test_sizes_are_the_programs_model_with_its_overrides():
    from benchmarks.runners.train import _program_config
    from dlrover_tpu.models import get_config

    config = _config()
    cfg = _program_config(config)  # raises on a size the program lacks
    assert cfg.layer_pattern == "S-L-L-L-" and cfg.n_layer == 4
    assert cfg.train_only.startswith("lightning (L) layers")
    assert cfg.num_params() == 1_184_654_336
    assert cfg.flops_per_token(16384) == flops.resolve(config, 16384)
    assert (cfg.select_groups, cfg.select_block, cfg.index_topk) == (2, 64, 64)
    # the multiplier keeps the published depth
    assert cfg.residual_scale == 1.4 / 32 ** 0.5
    full = get_config(config["program"]["model"])
    assert full.layer_pattern.startswith(cfg.layer_pattern)
    assert (full.n_layer, full.vocab_size, full.max_seq) == (
        32, 73448, 524288
    )
    assert full.residual_scale == cfg.residual_scale


def test_parameter_counts_in_the_file_are_inits_shapes():
    """The table in the configuration file against the shapes
    ``decoder.init`` would make (abstractly: nothing is allocated)."""
    import jax
    import numpy as np

    from benchmarks.runners.train import _program_config
    from dlrover_tpu.models import decoder, get_config

    config = _config()
    cfg = _program_config(config)
    shapes = jax.eval_shape(lambda: decoder.init(jax.random.key(0), cfg))
    count = lambda tree: sum(
        int(np.prod(t.shape)) for t in jax.tree.leaves(tree)
    )
    layers = shapes["layers"]
    table = config["parameters"]
    sparse = count(layers["sparse"]["attn"])
    lightning = count(layers["lightning"]["lin"]) // 3
    mlp = count(layers["mlp"]["mlp"])
    assert (sparse, lightning, mlp) == (52_429_056, 83_890_432, 201_326_592)
    assert count(layers["mlp.1"]["mlp"]) == 3 * mlp
    for key, number in (
        ("sparse_mixer", sparse), ("lightning_mixer", lightning),
        ("mlp", mlp), ("sparse_layer", sparse + mlp + 2 * 4096),
        ("lightning_layer", lightning + mlp + 2 * 4096),
        ("one_period", count(layers)),
        ("embedding_head_and_final_norm", count(shapes) - count(layers)),
        ("total", count(shapes)),
    ):
        assert f"{number:,}" in table[key], (key, number)
    assert count(shapes) == 1_184_654_336
    full = get_config(config["program"]["model"]).num_params()
    assert f"{full:,}" in table["total"]


def test_file_holds_the_published_keys_but_for_the_stated_cuts():
    config = _config()
    entry = _named(rehearsal._manifest()["configs"], CONFIG)
    assert sorted(entry["reduced"]) == sorted(config["reduced"])
    assert entry["source"] == config["source"] == SOURCE
    published = {
        "attention_bias": False, "attn_use_rope": False, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 4096,
        "intermediate_size": 16384, "lightning_head_dim": 128,
        "lightning_nh": 32, "lightning_nkv": 32,
        "lightning_scale": "1/sqrt(d)", "lightning_use_rope": True,
        "model_type": "minicpm_sala", "num_attention_heads": 32,
        "num_key_value_heads": 2, "qk_norm": True, "rand_init": False,
        "rms_norm_eps": 1e-06, "rope_theta": 10000, "scale_emb": 12,
        "scale_depth": 1.4, "mup_denominator": 32, "dim_model_base": 256,
        "tie_word_embeddings": False, "use_output_gate": True,
        "use_output_norm": True, "attn_use_output_gate": True,
    }
    assert {k: config[k] for k in published} == published
    cut = {
        "num_hidden_layers": 4, "vocab_size": 9181,
        "max_position_embeddings": 16384,
        "mixer_types": ["minicpm4"] + ["lightning-attn"] * 3,
    }
    assert {k: config[k] for k in cut} == cut
    for key in ("deployment", "parameters"):
        assert config[key]
    assert set(config["assumed"]) >= {
        "sparse_config", "selection_rule", "lightning_decay",
        "lightning_output_norm", "qk_norm", "rope_pairing",
        "lightning_scale", "layer_form", "weights", "param_dtype",
        "optimizer",
    }
    sizes = config["sizes"]
    assert (sizes["d_model"], sizes["d_ff"], sizes["vocab_size"]) == (
        config["hidden_size"], config["intermediate_size"],
        config["vocab_size"],
    )
    assert (sizes["n_head"], sizes["n_kv_head"], sizes["d_head"]) == (
        config["num_attention_heads"], config["num_key_value_heads"],
        config["head_dim"],
    )
    assert sizes["scale_emb"] == config["scale_emb"]
    assert sizes["residual_scale"] == (
        config["scale_depth"] / config["mup_denominator"] ** 0.5
    )
    assert sizes["logit_scale"] == (
        config["dim_model_base"] / config["hidden_size"]
    )
    assert sizes["norm_eps"] == config["rms_norm_eps"]
    assert (
        sizes["index_topk"], sizes["select_block"], sizes["select_groups"]
    ) == (64, 64, 2)
    assert (
        sizes["pool_window"], sizes["pool_stride"],
        sizes["select_init_blocks"], sizes["select_local"],
        sizes["select_dense_len"],
    ) == (32, 16, 1, 2048, 8192)
    assert config["check"] == {"kind": "selected"}
    assert config["reference"] == "minicpm_sala_plain"
    assert "eight" in config["deployment"].lower()


# ---- the readers ------------------------------------------------------------
# rows as a traced step of the cell names them (op_names of the compiled
# text: forward, the part's recomputation and the backward; the lightning
# layers inside a scanned run)

STEP = "jit(step_fn)/"
RUN = STEP + "transpose(jvp())/while/body/closed_call/checkpoint/"
BY_NAME = {
    "fusion.1 fusion s8[32,2,512,256]": [0.02, 3],
    "fusion.2 fusion s8[1,2,16384,16384]": [0.03, 6],
    "flash_fwd_sel.1 custom-call bf16[32,16384,128]": [0.06, 3],
    "flash_bwd_dq_sel.1 custom-call bf16[32,16384,128]": [0.09, 3],
    "flash_bwd_dkv_sel.1 custom-call bf16[32,16384,128]": [0.12, 3],
    "fusion.3 fusion bf16[16384,4096]": [0.10, 12],
    "ssd_fwd.1 custom-call bf16[1,16384,4096]": [0.04, 18],
    "ssd_bwd.1 custom-call bf16[1,16384,4096]": [0.08, 9],
    "fusion.4 fusion f32[16384,4096]": [0.30, 27],
    "fusion.5 fusion bf16[16384,16384]": [0.90, 36],
}
OP_NAMES = {
    "fusion.1 fusion s8[32,2,512,256]":
        STEP + "jvp(attn)/attn.block_select/while/body/ge",
    "fusion.2 fusion s8[1,2,16384,16384]": STEP + "jvp(attn)/broadcast_in_dim",
    "flash_fwd_sel.1 custom-call bf16[32,16384,128]":
        STEP + "jvp(attn)/flash_fwd_sel",
    "flash_bwd_dq_sel.1 custom-call bf16[32,16384,128]":
        STEP + "transpose(jvp(attn))/flash_bwd_dq_sel",
    "flash_bwd_dkv_sel.1 custom-call bf16[32,16384,128]":
        STEP + "transpose(jvp(attn))/flash_bwd_dkv_sel",
    "fusion.3 fusion bf16[16384,4096]":
        STEP + "transpose(jvp(attn))/attn.gate/dot_general",
    "ssd_fwd.1 custom-call bf16[1,16384,4096]":
        RUN + "rematted_computation/lin/ssm.scan/ssd_fwd",
    "ssd_bwd.1 custom-call bf16[1,16384,4096]":
        RUN + "lin/ssm.scan/ssd_bwd",
    "fusion.4 fusion f32[16384,4096]": RUN + "lin/dot_general",
    "fusion.5 fusion bf16[16384,16384]": RUN + "mlp/dot_general",
}


def _run(op_names=OP_NAMES, said=None, by_name=BY_NAME):
    from benchmarks.lib import peaks

    first = {
        "busy_s": 2.0, "by_name": by_name, "modules": ["jit_step_fn"],
        "op_names": {k: {v: by_name[k][0]} for k, v in op_names.items()},
    }
    said = [] if said is None else said
    return {
        "trace": {"per_device": [first]},
        "say": lambda **record: said.append(record),
        "sizes": _config()["sizes"], "seq": 16384,
        "window": {"tokens": 16384},
        "peaks": peaks.chip_peaks("TPU v5 lite"),
    }


@pytest.mark.parametrize(
    "metric,scope,rows,seconds",
    [
        ("sala.select_share", "attn.block_select", 1, 0.02),
        ("sala.sparse_attn_share", "attn", 6, 0.42),
        ("sala.linear_share", "lin", 3, 0.42),
        ("sala.scan_share", "ssm.scan", 2, 0.12),
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


def test_flash_roofline_counts_the_chosen_blocks_keys():
    read = _reader("sala.flash_roofline")
    # three traced steps: 3 calls of each kernel; 2, 3 and 4 products of
    # 2 x 128 operations over 32 heads x 16,384 queries x 3,560.5 keys
    pair_ops = 2.0 * 128 * 32 * 16384 * 3560.5
    want = 3 * (2 + 3 + 4) * pair_ops / (0.06 + 0.09 + 0.12) / 197e12
    assert read(_run()) == pytest.approx(100.0 * want)
    assert 0 < read(_run()) < 100
    assert read({"trace": None}) is None
    plain_kernels = {
        k.replace("_sel", ""): v for k, v in BY_NAME.items()
    }
    names = {k.replace("_sel", ""): v for k, v in OP_NAMES.items()}
    with pytest.raises(LookupError, match="flash_\\*_sel"):
        read(_run(op_names=names, by_name=plain_kernels))


# ---- run.py end to end at a tiny size ---------------------------------------

_OVERRIDES = {
    "n_layer": 4, "layer_pattern": "S-L-L-L-", "d_model": 128, "n_head": 4,
    "n_kv_head": 2, "d_head": 32, "d_ff": 256, "vocab_size": 512,
    "max_seq": 128, "sparse_block": 8, "index_topk": 4, "pool_window": 4,
    "pool_stride": 2, "select_init_blocks": 1, "select_local": 16,
    "select_dense_len": 32, "index_chunk": 32, "ssm_chunk": 32,
    "remat": "full", "attn_block_q": 128, "attn_block_k": 128,
}
TINY = {
    "source": "test",
    "program": {
        "model": "minicpm-sala",
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
         if k not in ("attn_block_q", "attn_block_k", "index_chunk",
                      "ssm_chunk", "sparse_block")},
        norm="rmsnorm", norm_eps=1e-6, act="swiglu", pos="rope",
        rope_theta=10000.0, tie_embeddings=False, qk_head_norm=True,
        attn_gate=True, select_block=8, select_groups=2, scale_emb=12.0,
        residual_scale=1.4 / 32 ** 0.5, logit_scale=256 / 4096,
    ),
    "reference": "minicpm_sala_plain",
    "check": {"kind": "selected"},
}
SELECTED_CHECKS = [
    "selection_valid", "selection_forced", "selection_regret",
    "selection_moved", "logits_vs_reference", "logits_rms_vs_reference",
    "loss_vs_reference", "lightning_fast_out_ms_vs_reference",
    "sparse_attn_out_ms_vs_reference", "loss_vs_free_reference", "first_step_loss", "no_compile_in_window",
    "no_failed_step",
]


def _this_cell_first(monkeypatch):
    """The rehearsal runs ``manifest["workloads"][0]``: here, this cell."""
    manifest = rehearsal._manifest()
    cell = _named(manifest["workloads"], CELL)
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
    assert list(checks) == SELECTED_CHECKS
    assert all(c["ok"] for c in checks.values()), checks
    assert result["correct"] is True and result["failed"] == 0
    ref = events["reference"]
    assert ref["kind"] == "selected" and ref["selection_faults"] == 0
    assert ref["select_forced_missing"] == 0
    assert ref["select_rows"] == {
        "block": 8, "groups": 2, "order": "layer-major, group-minor",
    }
    assert len(ref["select_moved_by_layer"]) == 2  # one layer, two KV heads
    assert ref["forced_logit_err"] < 1e-4
    assert set(ref["reference_terms"]) == {
        "sparse_attn_out_ms", "lightning_fast_out_ms",
    }
    assert checks["sparse_attn_out_ms_vs_reference"]["value"] < 1e-4
    assert checks["lightning_fast_out_ms_vs_reference"]["value"] < 1e-4
    if not trace:
        assert set(result["metrics"]) == {"train_tokens_per_s", "setup_s"}
        return
    listed = {
        m["name"] for m in manifest["per_layer"]
        if "workloads" not in m or CELL in m["workloads"]
    }
    assert set(READERS) <= listed
    # no device plane on the CPU: the trace readers return nothing and
    # the line leaves them out
    assert set(result["metrics"]) <= listed
    assert not set(READERS) & set(result["metrics"])


CHUNK = 256  # ``pallas_ssd.CHUNKS``' first: the chip's


@pytest.mark.parametrize("defect", sorted(defects.INJECT))
def test_comparison_fails_by_a_named_check(monkeypatch, capsys, defect):
    """Sound, the tiny cell reads 1e-6 on the logits and no regret; each
    defect has to push one of ITS checks past the CHIP's limits, which
    are the ones ``run.py`` holds."""
    _this_cell_first(monkeypatch)
    config = TINY
    if defect == "decay_bf16":
        # eight bits of a running sum show once the sum is long: the
        # chunk of 256 tokens the kernels take on the chip (the tiny
        # one's 32 leaves the sum under 13), and SIX heads (at 4 or 8 the slopes
        # -2^(-8 h / n_head) are powers of two, whose running sums bf16
        # holds exactly): the fastest head's sum reaches 102, the
        # chip's 215
        monkeypatch.setattr(rehearsal, "TINY_TRAFFIC", dict(
            rehearsal.TINY_TRAFFIC, global_batch=1, seq=CHUNK
        ))
        wider = dict(max_seq=CHUNK, n_head=6)
        over = dict(TINY["program"]["overrides"], ssm_chunk=CHUNK, **wider)
        config = dict(
            TINY, sizes=dict(TINY["sizes"], **wider),
            program=dict(TINY["program"], overrides=over),
        )
    defects.INJECT[defect](monkeypatch.setattr)
    rc, _, _, lines = rehearsal._run_patched(monkeypatch, capsys, config, 0)
    assert rc == 0
    checks, _ = rehearsal._events(lines)
    failed = {name for name, c in checks.items() if not c["ok"]}
    assert failed & set(defects.CAUGHT_BY[defect]), (defect, checks)
    assert json.loads(lines[-1])["correct"] is False


def test_defects_name_checks_the_comparison_makes():
    assert set(defects.INJECT) == set(defects.CAUGHT_BY)
    for named in defects.CAUGHT_BY.values():
        assert named and set(named) <= set(SELECTED_CHECKS)
    # the selection's defects are PR 56's that a program can have
    from benchmarks.tests.defects import BLOCK_CAUGHT_BY

    shared = set(BLOCK_CAUGHT_BY) & set(defects.CAUGHT_BY)
    assert shared == {
        "recent_blocks", "one_head_scores", "block_mean", "pool_no_overlap",
        "group0_for_both", "initial_dropped", "local_dropped",
        "blocks_ignored",
    }
    for name in shared - {"blocks_ignored"}:
        assert set(BLOCK_CAUGHT_BY[name]) <= set(defects.CAUGHT_BY[name])
