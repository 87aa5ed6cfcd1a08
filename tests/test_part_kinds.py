"""A kind of ``layer_pattern`` part is one record a side:
``decoder.PARTS`` (what the trunk does with a letter) and
``config.PART_RULES`` (what ``ModelConfig`` asks of it), beside
``ModelConfig._part_counts``. The pairings the if-chains kept by hand,
a case a letter, and the seam itself: a new letter is an entry in each
and nothing else."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.models import config, decoder
from dlrover_tpu.models.config import PART_RULES, ModelConfig
from dlrover_tpu.models.decoder import PARTS

SEQ = 32
# every kind's sizes at tier-1 widths, but the block-sparse attention's:
# a pattern without an S part refuses them
SIZES = dict(
    name="kinds", vocab_size=256, n_layer=1, d_model=64, n_head=4,
    n_kv_head=2, d_head=16, d_ff=128, max_seq=SEQ, dtype="float32",
    mamba_num_heads=8, mamba_head_dim=8, ssm_state_size=16, n_groups=2,
    ssm_chunk=16, mamba_expand=2, mamba_dt_rank=8, gdn_key_heads=2,
    gdn_value_heads=4, gdn_key_dim=8, gdn_value_dim=8, kda_heads=4,
    kda_head_dim=8, kda_gate_rank=8, n_experts=8, expert_top_k=2,
    d_expert=32, moe_impl="ragged",
)
SPARSE = dict(
    sparse_block=8, index_topk=4, pool_window=4, pool_stride=2,
    select_init_blocks=1, select_local=16, select_dense_len=32,
    index_chunk=16,
)


def _axes_structure(axes):
    """The structure of a tree of logical-axis tuples."""
    return jax.tree.structure(axes, is_leaf=lambda t: isinstance(t, tuple))


def _one_part(letter, **over):
    """A model of one layer of one part of the kind."""
    sizes = {**SIZES, **(SPARSE if letter == "S" else {}), **over}
    return ModelConfig(layer_pattern=letter, **sizes)


@pytest.mark.parametrize("letter", PARTS)
def test_a_kinds_axes_follow_its_parameters(letter):
    cfg = _one_part(letter)
    params = jax.eval_shape(
        lambda: decoder.init(jax.random.key(0), cfg)
    )["layers"]
    axes = decoder.logical_axes(cfg)["layers"]
    assert list(params) == [PARTS[letter].stack]
    assert set(params[PARTS[letter].stack]) == {"ln", PARTS[letter].key}
    assert jax.tree.structure(params) == _axes_structure(axes)
    names = jax.tree.leaves(axes, is_leaf=lambda t: isinstance(t, tuple))
    for leaf, axis_names in zip(jax.tree.leaves(params), names):
        assert leaf.ndim == len(axis_names) and axis_names[0] == "layers"


def test_the_tables_name_the_same_letters():
    cfg = _one_part("*")
    assert PARTS.keys() == PART_RULES.keys() == cfg._part_counts().keys()


@pytest.mark.parametrize("letter", [c for c in PARTS if PARTS[c].read])
def test_a_mixer_hands_its_read_out(letter):
    cfg = _one_part(letter)
    params = decoder.init(jax.random.key(0), cfg)["layers"]
    layer = jax.tree.map(lambda t: t[0], params[PARTS[letter].stack])
    x = jax.random.normal(jax.random.key(1), (2, SEQ, cfg.d_model))
    positions = jnp.broadcast_to(jnp.arange(SEQ, dtype=jnp.int32), (2, SEQ))
    out, aux = decoder._part_body(
        x, layer, positions, letter=letter, cfg=cfg, mesh=None,
        attn_fn=None,
        rope=decoder._rope_tables(positions, cfg.rope_dim, cfg.rope_theta),
    )
    assert out.shape == x.shape and np.isfinite(np.asarray(out)).all()
    assert aux[PARTS[letter].read].shape == ()
    # and the trunk reports it under that name
    _, trunk_aux = decoder.run_trunk(
        x, params, positions, cfg, attn_fn=None
    )
    np.testing.assert_allclose(
        trunk_aux[PARTS[letter].read], aux[PARTS[letter].read], rtol=1e-6
    )


@pytest.mark.parametrize("letter", PART_RULES)
def test_only_attention_and_mlp_kinds_leave_the_refusal_to_the_trunk(letter):
    """A mixer with a state, or a selection, says itself why the cache
    paths refuse it; an attention, an MLP and the routed experts leave
    it to 'a trunk whose layers differ'."""
    rule = PART_RULES[letter]
    assert bool(rule.train_only) == (letter not in "*-Ee")
    why = _one_part(letter).train_only
    assert why == (rule.train_only or "a trunk whose layers differ")
    # a kind the trunk hands something over for is not a module's
    kind = PARTS[letter]
    assert rule.trunk_only == bool(kind.read or kind.selects)


@pytest.mark.parametrize("letter", PARTS)
def test_a_kind_that_rides_out_is_never_scanned(letter):
    runs = decoder._pattern_runs(letter * 3)
    if PARTS[letter].rides_out:
        assert runs == [(letter, 1)] * 3
    else:
        assert runs == [(letter, 3)]


def test_a_new_letter_is_an_entry_a_table(monkeypatch):
    """The seam: a throwaway kind ``Z`` (one [d, d] matrix, starting as
    the identity) is ONE entry in ``PARTS``, ONE in ``PART_RULES`` and
    one row of ``_part_counts`` — and then a model of it initialises,
    scans its two layers and gives every parameter a gradient."""
    def init(key, cfg, lead):
        eye = jnp.eye(cfg.d_model, dtype=cfg.param_dtype)
        return {"w": jnp.broadcast_to(eye, tuple(lead) + eye.shape)}

    monkeypatch.setitem(PARTS, "Z", decoder.PartKind(
        stack="zed", key="zed", init=init,
        axes=lambda cfg, lead: {"w": lead + ("embed", None)},
        run=lambda h, p, c: (h @ p["zed"]["w"].astype(h.dtype), {}),
    ))
    monkeypatch.setitem(PART_RULES, "Z", config.PartRule("a throwaway"))
    counts = ModelConfig._part_counts
    monkeypatch.setattr(ModelConfig, "_part_counts", lambda self: {
        **counts(self),
        "Z": (self.d_model ** 2 + self.d_model, self.d_model ** 2),
    })
    cfg = _one_part("Z-Z-", n_layer=2, pos="none")
    assert decoder._pattern_runs(cfg.layer_pattern) == [("Z-", 2)]
    params = decoder.init(jax.random.key(0), cfg)
    assert sorted(params["layers"]) == ["mlp", "zed"]
    assert params["layers"]["zed"]["zed"]["w"].shape == (2, 64, 64)
    assert jax.tree.structure(params) == _axes_structure(
        decoder.logical_axes(cfg)
    )
    assert cfg.num_params() == sum(t.size for t in jax.tree.leaves(params))
    tokens = jax.random.randint(jax.random.key(1), (2, SEQ), 0, 256)
    batch = {"tokens": tokens, "targets": jnp.roll(tokens, -1, axis=1)}
    loss, grads = jax.value_and_grad(
        lambda p: decoder.loss_fn(p, batch, cfg)[0]
    )(params)
    assert np.isfinite(float(loss))
    assert all(
        float(jnp.abs(g).max()) > 0 for g in jax.tree.leaves(grads["layers"])
    )
    # and a letter with no entry is still refused
    with pytest.raises(ValueError, match="is made of"):
        dataclasses.replace(cfg, layer_pattern="Y-Z-")
