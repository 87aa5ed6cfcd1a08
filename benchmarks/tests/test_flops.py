"""The required-FLOPs function against hand arithmetic for the three
configurations, and the peak table raising on a chip it does not know."""

import json
import os

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


# the committed configurations, to the last bit: what
# ``required_flops_per_token`` gives on the parent of PR 28 (commit 625dd4b)
PARENT = {
    "gpt2-xl": (1024, 9802598400.0),
    "mistral-7b-l6": (8192, 9544212480.0),
    "gpt2-xl-zero1-dp4": (1024, 5142758400.0),
}


@pytest.mark.parametrize("name", sorted(PARENT))
def test_dense_counts_did_not_move(name):
    seq, parent = PARENT[name]
    assert flops.required_flops_per_token(_sizes(name), seq) == parent
