"""The required-FLOPs convention against hand arithmetic: the committed
configurations through the old call and through the runner's
resolution, an architecture whose layers are not all alike through a
``required_terms`` of its own, and the peak table raising on a chip it
does not know."""

import json
import os
import sys
import types

import pytest

from benchmarks.lib import flops, peaks

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(__file__)), "configs")


def _config(name):
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        return json.load(f)


def _sizes(name):
    return _config(name)["sizes"]


# the source's own keys at the top of a configuration file, and the key
# of ``sizes`` that has to say the same
SOURCE_KEYS = {
    "n_layer": "n_layer", "n_embd": "d_model", "n_head": "n_head",
    "n_positions": "max_seq", "vocab_size": "vocab_size",
    "layer_norm_epsilon": "norm_eps", "tie_word_embeddings": "tie_embeddings",
    "hidden_size": "d_model", "intermediate_size": "d_ff",
    "num_hidden_layers": "n_layer", "num_attention_heads": "n_head",
    "num_key_value_heads": "n_kv_head", "sliding_window": "attn_window",
    "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps",
}


@pytest.mark.parametrize(
    "name", sorted(f[:-5] for f in os.listdir(CONFIGS) if f.endswith(".json"))
)
def test_source_keys_agree_with_sizes(name):
    config = _config(name)
    shared = [k for k in SOURCE_KEYS if k in config]
    assert len(shared) >= 5
    for key in shared:
        assert config[key] == config["sizes"][SOURCE_KEYS[key]], key


def test_mean_span():
    # causal, no window: (1 + 2 + ... + s) / s
    assert flops.mean_span(1024) == 512.5
    assert flops.mean_span(4, 0) == 2.5
    # window 2 over 4 queries: 1, 2, 2, 2 keys
    assert flops.mean_span(4, 2) == 7 / 4
    # Mistral: 4096 queries see 1..4096 keys, 4096 more see 4096 each
    assert flops.mean_span(8192, 4096) == pytest.approx(
        (4096 * 4097 / 2 + 4096 * 4096) / 8192
    )
    assert flops.mean_span(8192, 4096) == pytest.approx(3072.25)
    # a window wider than the sequence is no window
    assert flops.mean_span(1024, 4096) == 512.5


def test_mean_span_of_a_selection():
    # clause (i): query i attends to min(i + 1, k) keys. k = 2 over 4
    # queries: 1, 2, 2, 2 keys, as a window of 2 would give
    assert flops.mean_span(4, 0, 2) == 7 / 4
    # Keye-VL-2.0's language tower at s 8192, top-2048: 2048 queries see
    # 1..2048 keys, 6144 more see 2048 each = 14,681,088 pairs
    assert 2048 * 2049 // 2 + 6144 * 2048 == 14_681_088
    assert flops.mean_span(8192, 0, 2048) == 14_681_088 / 8192 == 1792.125
    # the narrower of window and selection caps the span
    assert flops.mean_span(8192, 4096, 2048) == 1792.125
    assert flops.mean_span(8192, 1024, 2048) == flops.mean_span(8192, 1024)
    # no selection, or one wider than the sequence, is the causal span:
    # every existing caller reads what it read
    for seq, window in ((1024, 0), (8192, 4096), (4096, 0), (4, 2)):
        assert flops.mean_span(seq, window, 0) == flops.mean_span(seq, window)
        assert flops.mean_span(seq, window, seq) == flops.mean_span(seq, window)
    assert flops.mean_span(8192, topk=2048) == 1792.125


def test_mean_span_of_a_selection_by_blocks():
    """``block`` > 1: ``topk`` counts blocks, the query's own among them,
    and a query sees the keys of its own up to itself."""
    def brute(seq, k, b):
        return sum(
            (min(i // b + 1, k or seq) - 1) * b + i % b + 1
            for i in range(seq)
        ) / seq

    for seq, k, b in ((64, 2, 8), (64, 0, 8), (64, 100, 16), (256, 6, 16),
                      (16384, 64, 64)):
        assert flops.mean_span(seq, 0, k, b) == brute(seq, k, b)
    # MiniCPM-SALA's sparse layer at 16,384: row t >= 4,096 holds
    # 63 x 64 + t mod 64 + 1 keys
    assert flops.mean_span(16384, topk=64, block=64) == 3560.5
    # blocks of one key are keys: the old count, bit for bit
    for seq, k in ((8192, 2048), (4, 2), (128, 0)):
        assert flops.mean_span(seq, 0, k, 1) == flops.mean_span(seq, 0, k)
    # every block chosen is the causal span
    assert flops.mean_span(256, block=16) == flops.mean_span(256)
    for bad in ((100, 0, 2, 16), (256, 64, 2, 16)):
        with pytest.raises(ValueError):
            flops.mean_span(*bad)


def test_a_selecting_layer_and_its_indexer_by_hand():
    """Clauses (i) and (ii) at Keye-VL-2.0's language widths (32 heads
    of 128 score and 128 value channels; an indexer of 16 heads of 64
    score channels and no value product, which scores every visible
    key), s 8192, top-2048."""
    attention = 32 * (128 + 128) / 2 * flops.mean_span(8192, 0, 2048)
    assert attention == 7_340_544
    indexer = 16 * (64 + 0) / 2 * flops.mean_span(8192)
    assert indexer == 2_097_408
    assert round(12 * attention / 1e6, 1) == 88.1  # MFLOP a token
    assert round(12 * indexer / 1e6, 1) == 25.2
    # what the whole causal span would have credited the layer with
    assert round(12 * 32 * 128 * flops.mean_span(8192) / 1e6, 1) == 201.4
    # the indexer's projections (q 2048 x 16 x 64, k 2048 x 64, head
    # weights 2048 x 16) are multiplied parameters like any other
    terms = {
        "multiplied_params": 2048 * (16 * 64 + 64 + 16),
        "attention_pair_channels": attention + indexer,
    }
    assert flops.flops_of(terms) == 6.0 * 2_260_992 + 12.0 * 9_437_952


# per layer, by hand:
# GPT-2 XL: q, k, v, o 4 x 1600^2 = 10.24 M; MLP 2 x 1600 x 6400 = 20.48 M
# Mistral: q, o 2 x 4096^2 = 33.554 M; k, v 2 x 4096 x 1024 = 8.389 M;
#          gate, up, down 3 x 4096 x 14336 = 176.161 M
HAND = {
    "gpt2-xl": dict(
        seq=1024,
        multiplied=48 * (10_240_000 + 20_480_000) + 1600 * 50304,
        attention=12 * 48 * 1600 * 512.5,
        gflop=9.80,
    ),
    "mistral-7b-l6": dict(
        seq=8192,
        multiplied=6 * (33_554_432 + 8_388_608 + 176_160_768) + 4096 * 32000,
        attention=12 * 6 * 4096 * 3072.25,
        gflop=9.54,
    ),
    "gpt2-xl-zero1-dp4": dict(
        seq=1024,
        multiplied=24 * (10_240_000 + 20_480_000) + 1600 * 50304,
        attention=12 * 24 * 1600 * 512.5,
        gflop=5.14,
    ),
}


@pytest.mark.parametrize("name", sorted(HAND))
def test_required_flops_match_hand_arithmetic(name):
    hand, sizes = HAND[name], _sizes(name)
    assert flops.multiplied_params(sizes) == hand["multiplied"]
    need = flops.required_flops_per_token(sizes, hand["seq"])
    assert need == pytest.approx(6 * hand["multiplied"] + hand["attention"])
    assert round(need / 1e9, 2) == hand["gflop"]


def test_peak_table():
    v5e = peaks.chip_peaks("TPU v5 lite")
    assert v5e.bf16_flops == 197e12 and v5e.hbm_bytes_s == 819e9
    assert v5e.ici_bits_s == 1600e9 and v5e.hbm_bytes == 16e9
    assert peaks.chip_peaks("TPU v5e") == v5e
    for unknown in ("cpu", "TPU v7x", "NVIDIA H100"):
        with pytest.raises(KeyError):
            peaks.chip_peaks(unknown)


# OLMoE-1B-7B's widths (allenai/OLMoE-1B-7B-0125-Instruct) at 3 layers:
# attention 4 x 2048^2 = 16.777 M; one expert 3 x 2048 x 1024 = 6.291 M,
# eight of 64 per token; router 2048 x 64 = 0.131 M; head 2048 x 50304
OLMOE = {
    "n_layer": 3, "d_model": 2048, "n_head": 16, "n_kv_head": 16,
    "d_ff": 1024, "vocab_size": 50304, "act": "swiglu", "attn_window": 0,
}


def test_routed_layers_count_what_a_token_meets():
    routed = dict(OLMOE, n_experts=64, expert_top_k=8)
    by_hand = 3 * (16_777_216 + 8 * 6_291_456 + 131_072) + 2048 * 50304
    assert flops.multiplied_params(routed) == by_hand
    attention = 12 * 3 * 2048 * 2048.5
    need = flops.required_flops_per_token(routed, 4096)
    assert need == pytest.approx(6 * by_hand + attention)
    assert round(need / 1e9, 3) == 1.979
    # without the keys, or with no experts, a layer's MLP is one dense
    # block of d_ff: the count of PR 24
    for dense in (OLMOE, dict(OLMOE, n_experts=0, expert_top_k=8)):
        assert flops.multiplied_params(dense) == (
            3 * (16_777_216 + 6_291_456) + 2048 * 50304
        )
        assert round(
            flops.required_flops_per_token(dense, 4096) / 1e9, 3
        ) == 1.184


# the four configurations committed before PR 33, to the last bit: what
# ``required_flops_per_token`` gives on the parent of PR 28 (commit
# 625dd4b) and, for OLMoE, on the parent of PR 33 (commit e8a7159). A
# configuration added later is not listed here: it brings its hand
# arithmetic in a test file of its own
PARENT = {
    "gpt2-xl": (1024, 9802598400.0),
    "mistral-7b-l6": (8192, 9544212480.0),
    "gpt2-xl-zero1-dp4": (1024, 5142758400.0),
    "olmoe-1b-7b-1chip": (4096, 1979486208.0),
}
# the fifth, since PR 34, is counted by its reference module's
# ``required_terms`` alone: what ``resolve`` gives on the parent of PR 36
RESOLVED = dict(PARENT, **{"glm-4.7-flash-ep8-1chip": (8192, 5497466880.0)})


@pytest.mark.parametrize("how", ["old_call", "resolved"])
@pytest.mark.parametrize("name", sorted(PARENT))
def test_committed_counts_did_not_move(name, how):
    """No committed reference module defines ``required_terms``: the
    runner's resolution and the old call both give the parent's count."""
    seq, parent = PARENT[name]
    if how == "old_call":
        assert flops.required_flops_per_token(_sizes(name), seq) == parent
    else:
        assert flops.resolve(_config(name), seq) == parent


def test_every_committed_configuration_resolves_to_its_count():
    """PR 36 gave ``mean_span`` a third argument: all five committed
    configurations read their counts to the last bit."""
    for name, (seq, count) in sorted(RESOLVED.items()):
        assert flops.resolve(_config(name), seq) == count, name


# ---- an architecture whose layers are not all alike ----------------------
# GLM-4.7-Flash's published widths (zai-org/GLM-4.7-Flash config.json,
# ``glm4_moe_lite``), as data: no configuration file. Hidden 2048; latent
# attention, 20 heads: q through rank 768 to 20 x (192 + 64) channels, k
# and v through rank 512 (+ 64 shared rope channels) to 20 x (192 + 256),
# out 20 x 256 -> 2048; one leading dense SwiGLU layer of width 10240,
# then routed layers of 64 SwiGLU experts of width 1536, top-4, beside one
# shared expert of the same width; one multi-token-prediction module (a
# 2 x 2048 -> 2048 projection, one routed block, the head once more).
# The cut of ISSUE 33: 1 dense + 8 routed layers + the module, this chip
# holding 8 of the 64 experts and 1/8 of the vocabulary (19,360 rows),
# sequences of 8192.
GLM = {
    "d_model": 2048, "n_head": 20, "q_lora_rank": 768, "kv_lora_rank": 512,
    "qk_nope_head_dim": 192, "qk_rope_head_dim": 64, "v_head_dim": 256,
    "n_dense_layer": 1, "d_ff": 10240, "n_routed_layer": 8,
    "d_expert": 1536, "n_experts": 64, "n_experts_held": 8,
    "expert_top_k": 4, "n_shared_experts": 1, "n_mtp_module": 1,
    "vocab_size": 19360, "act": "swiglu", "attn_window": 0,
}


def glm_required_terms(sizes, seq):
    """What a ``references/<module>.py`` for this architecture would
    define, by the clauses of ``lib/flops.py``'s convention."""
    d, h = sizes["d_model"], sizes["n_head"]
    qk = sizes["qk_nope_head_dim"] + sizes["qk_rope_head_dim"]
    attn = (
        d * sizes["q_lora_rank"] + sizes["q_lora_rank"] * h * qk
        + d * (sizes["kv_lora_rank"] + sizes["qk_rope_head_dim"])
        + sizes["kv_lora_rank"]
        * h * (sizes["qk_nope_head_dim"] + sizes["v_head_dim"])
        + h * sizes["v_head_dim"] * d
    )
    expert = 3 * d * sizes["d_expert"]
    met = (  # routed experts a token meets on this chip, and the shared
        sizes["expert_top_k"] * sizes["n_experts_held"] / sizes["n_experts"]
        + sizes["n_shared_experts"]
    )
    routed = attn + d * sizes["n_experts"] + met * expert
    dense = attn + 3 * d * sizes["d_ff"]
    head = d * sizes["vocab_size"]
    mtp = sizes["n_mtp_module"] * (2 * d * d + routed + head)
    attn_layers = (
        sizes["n_dense_layer"] + sizes["n_routed_layer"]
        + sizes["n_mtp_module"]
    )
    return {
        "multiplied_params": int(
            sizes["n_dense_layer"] * dense
            + sizes["n_routed_layer"] * routed + mtp + head
        ),
        "attention_pair_channels": (
            attn_layers * h * (qk + sizes["v_head_dim"]) / 2
            * flops.mean_span(seq, sizes["attn_window"])
        ),
    }


def test_layers_that_differ_are_counted_kind_by_kind(monkeypatch):
    # by hand, per layer: attention 1,572,864 + 3,932,160 + 1,179,648
    # + 4,587,520 + 10,485,760 = 21,757,952; dense MLP 62,914,560; one
    # expert 9,437,184; router 131,072. A routed layer on this chip:
    # attention + router + (4 x 8 / 64 + 1) experts = 36,044,800. The
    # module: 8,388,608 + one routed layer + the head.
    terms = glm_required_terms(GLM, 8192)
    by_hand = (
        (21_757_952 + 62_914_560) + 8 * 36_044_800
        + (8_388_608 + 36_044_800 + 2048 * 19360) + 2048 * 19360
    )
    assert by_hand == 496_762_880
    assert terms["multiplied_params"] == by_hand
    assert terms["attention_pair_channels"] == 10 * 5120 * 4096.5
    assert round(flops.flops_of(terms) / 1e9, 3) == 5.497
    # through the runner's resolution: a reference module that defines
    # ``required_terms`` is counted by it
    module = types.SimpleNamespace(required_terms=glm_required_terms)
    monkeypatch.setitem(sys.modules, "benchmarks.references.glm_test", module)
    config = {"reference": "glm_test", "sizes": GLM}
    assert flops.resolve(config, 8192) == 6.0 * by_hand + 12.0 * (
        10 * 5120 * 4096.5
    )
    # THE DEFECT ISSUE 33 REPAIRS, pinned: the built-in count on the same
    # model in the program's vocabulary (``intermediate_size`` 10240 is
    # ``d_ff``; 9 layers) takes head_dim 2048 // 20 = 102 for 256 and
    # four experts of the DENSE width in every layer: 2.84 times
    builtin = {
        "n_layer": 9, "d_model": 2048, "n_head": 20, "n_kv_head": 20,
        "d_ff": 10240, "vocab_size": 19360, "act": "swiglu",
        "attn_window": 0, "n_experts": 64, "expert_top_k": 4,
    }
    wrong = flops.required_flops_per_token(builtin, 8192)
    assert round(wrong / 1e9, 2) == 15.64
    assert round(wrong / flops.flops_of(terms), 2) == 2.84


@pytest.mark.parametrize("terms", [
    {"multiplied_params": 10},
    {"multiplied_params": 10, "attention_pair_channels": 1.0, "extra": 1},
    {"multiplied_params": 0, "attention_pair_channels": 1.0},
    {"multiplied_params": 10, "attention_pair_channels": float("nan")},
])
def test_terms_outside_the_convention_are_refused(terms):
    with pytest.raises(ValueError):
        flops.flops_of(terms)
