"""A block-sparse attention on the program, for the tests, from a
program that selects keys or nothing: ``selection_tap.py``'s way, in
UNITS of ``select_block`` keys.

``install`` replaces ``decoder._attention_block`` by a block that
scores blocks of keys as the block stand-in does (``block_standin``:
pooled keys, a softmax over those whose window has ended, the sum over
the query heads, the max over a block's pooled keys — no new
parameter), takes the forced units and the best of the others and
attends under the chosen blocks' keys in plain ``jax.numpy``;
``logits_and_choices`` is ``selection_tap``'s, whose ordered host
callback carries each layer's units out. ONE selection a layer
(``select_groups`` 1: every query head's scores summed), because the
program has no path that is split by KV head for the tap to stand in;
two selections a layer are the stand-in's (``test_selected.py``).
``reference`` is ``block_plain`` on the program's own parameters. Not
part of the benchmark: ``run.py`` refuses such a configuration on a
program that hands keys over, by the shape it should have had.
"""

import jax
import jax.numpy as jnp

from benchmarks.tests import block_plain, block_standin, selection_tap

# the selection's sizes, in the configuration file's vocabulary: 4 of a
# query's blocks of 16 keys, the first and a local window of 2 forced
UNITS = {
    "index_topk": 4, "select_block": 16, "select_groups": 1,
    "pool_window": 8, "pool_stride": 4, "select_init_blocks": 1,
    "select_local": 32,
}
reference = block_plain
# the tap that carries a layer's rows out takes units as it takes keys
logits_and_choices = selection_tap.logits_and_choices


def install(patch):
    """Make every attention layer of the program select by blocks."""
    from dlrover_tpu.models import decoder

    f32 = jnp.float32

    def block(x, layer, cfg, mesh, positions, attn_fn, fp8=None, rope=None):
        b, s, _ = x.shape
        nh, hd, size = cfg.n_head, cfg.head_dim, UNITS["select_block"]
        q, k, v = decoder._project_qkv(
            x, layer, cfg, positions, fp8=fp8, rope=rope
        )
        pooled = block_standin._pooled_keys(
            k, UNITS["pool_window"], UNITS["pool_stride"]
        )
        qpos = jnp.arange(s)
        index = block_standin._unit_scores(q, pooled, qpos, UNITS, s // size)
        chosen = block_standin._select_units(
            index, block_standin._forced_units(qpos, s // size, UNITS),
            UNITS["index_topk"], qpos,
        )  # [B, 1, S, U]
        if selection_tap._emit is not None:
            selection_tap._emit(chosen[:, 0])
        rep = nh // k.shape[2]
        k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
        keys = jnp.repeat(chosen, size, axis=-1) & (
            jnp.arange(s)[None, :] <= qpos[:, None]
        )
        scores = jnp.einsum(
            "bqhd,bkhd->bhqk", q.astype(f32), k.astype(f32)
        ) * hd ** -0.5
        probs = jax.nn.softmax(jnp.where(keys, scores, -jnp.inf), axis=-1)
        out = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(x.dtype), v)
        return out.reshape(b, s, nh * hd) @ layer["attn"]["wo"].astype(x.dtype)

    patch(decoder, "_attention_block", block)

