"""Plain reference for AI21-Jamba2-3B (``model_type: jamba``;
https://huggingface.co/ai21labs/AI21-Jamba2-3B/blob/main/config.json):
the forward pass and the mean next-token loss in straightforward
``jax.numpy`` and float32, written LAYER BY LAYER as published. No
kernel, no chunk, no scan over layers, no remat. The caller runs it
under ``jax.default_matmul_precision("highest")``.

Every layer i of the trunk is pre-norm twice:

    x <- x + mixer_i(rms(x; g1_i))
    x <- x + W_down (silu(h W_gate) * (h W_up)),   h = rms(x; g2_i)
    logits = rms(x; g_f) E^T          (E the embedding: the head is tied)

The mixer is an attention where i mod attn_layer_period (14) =
attn_layer_offset (7): n_head query heads q_h = h W_q[h] on ONE key and
value head of 128 channels, causal softmax at scale 128^-1/2, no
position term, no bias. Everywhere else it is a Mamba-1 mixer, on h
[S, d] with C = mamba_expand * d channels of N = mamba_d_state states:

    [u | z] = h W_in                        (u first, no bias)
    u_t <- silu( sum_{j<K} w_j * u_{t-K+1+j} + b )     (depthwise,
           causal: zeros before the first token)
    [r | B | C] = u W_x                     (dt_rank | N | N, no bias)
    r, B, C = rms(r; g_dt), rms(B; g_B), rms(C; g_C)
    D_t = softplus(r_t W_dt + b_dt);   A = -exp(A_log)     ([C, N])
    s_t[c, n] = exp(D_t[c] A[c, n]) s_{t-1}[c, n] + D_t[c] u_t[c] B_t[n]
    y_t[c] = sum_n C_t[n] s_t[c, n] + D[c] u_t[c]           (s_0 = 0)
    out = (y * silu(z)) W_out

The recurrence is run AS WRITTEN, token by token (``lax.scan`` over t on
the state [C, N]): the program's chunks and its hand-written derivative
are another algorithm for the same numbers.

It reads the program's parameter tree by name (``layers`` holding the
parts kind by kind: ``mamba1``, ``attention``, ``mlp``, each part with
its own norm ``ln``; a kind's parts may lie in several stacks end to
end, ``mlp``, ``mlp.1``, ``mlp.2``: ``_part`` counts through them) and
the configuration file's ``sizes``, whose ``layer_pattern`` spells the
layers two letters each: ``m-`` a Mamba-1 layer, ``*-`` the attention
layer. Layer i takes the next of its mixer's kind and the i-th MLP.

``required_terms`` counts, beside the matrices, the recurrence's own
work as ``nemotron_h_plain`` enters its own: the state update and the
read-out are 2 * C * N multiply-adds a token a layer (163,840 at the
published sizes, 0.13% of the total), entered as that many MULTIPLIED
PARAMETERS. The conv's K taps a channel are not counted.
"""

import jax
import jax.numpy as jnp

from benchmarks.lib import flops
from benchmarks.references.decoder_plain import F32, _attention, _norm


def _layers(sizes):
    """The mixer's letter of every layer, from ``layer_pattern``."""
    pattern = sizes["layer_pattern"]
    if (
        len(pattern) != 2 * sizes["n_layer"]
        or set(pattern[1::2]) != {"-"} or set(pattern[0::2]) - set("m*")
    ):
        raise ValueError(
            f"{pattern!r} is not {sizes['n_layer']} layers of a mixer "
            "(m or *) and an MLP (-) each"
        )
    return pattern[0::2]


def required_terms(sizes, seq):
    """The two terms of ``lib/flops.py``'s convention: layers counted
    kind by kind, the recurrence as the docstring says, the tied head
    once."""
    d, n = sizes["d_model"], sizes["ssm_state_size"]
    inner, rank = sizes["mamba_expand"] * d, sizes["mamba_dt_rank"]
    d_attn = sizes["n_head"] * sizes["d_head"]
    mixer = {
        "m": (
            d * 2 * inner + inner * (rank + 2 * n) + rank * inner
            + inner * d + 2 * inner * n
        ),
        "*": 2 * d * d_attn + 2 * d * sizes["n_kv_head"] * sizes["d_head"],
    }
    mlp = 3 * d * sizes["d_ff"]
    kinds = _layers(sizes)
    return {
        "multiplied_params": int(
            sum(mixer[c] + mlp for c in kinds) + d * sizes["vocab_size"]
        ),
        "attention_pair_channels": (
            kinds.count("*") * d_attn * flops.mean_span(seq)
        ),
    }


def _f32(tree):
    return jax.tree.map(lambda w: w.astype(F32), tree)


def _rms(x, p, sizes):
    return _norm(x, p, "rmsnorm", sizes["norm_eps"])


def _mamba(h, p, sizes):
    """h [B, S, d] -> the mixer's output [B, S, d]."""
    s = h.shape[1]
    n, rank, taps = (
        sizes["ssm_state_size"], sizes["mamba_dt_rank"], sizes["conv_kernel"]
    )
    u, z = jnp.split(h @ p["w_in"], 2, axis=-1)
    padded = jnp.pad(u, ((0, 0), (taps - 1, 0), (0, 0)))
    u = jax.nn.silu(
        sum(padded[:, j:j + s] * p["conv_w"][j] for j in range(taps))
        + p["conv_b"]
    )
    r, b_mat, c_mat = jnp.split(u @ p["w_x"], [rank, rank + n], axis=-1)
    r = _rms(r, p["dt_norm"], sizes)
    b_mat = _rms(b_mat, p["b_norm"], sizes)
    c_mat = _rms(c_mat, p["c_norm"], sizes)
    step = jax.nn.softplus(r @ p["w_dt"] + p["dt_bias"])      # [B, S, C]
    a = -jnp.exp(p["a_log"])                                  # [C, N]

    def token(state, inp):
        u_t, step_t, b_t, c_t = inp
        state = jnp.exp(step_t[..., None] * a) * state + (
            (step_t * u_t)[..., None] * b_t[:, None, :]
        )
        return state, jnp.sum(state * c_t[:, None, :], axis=-1)

    _, y = jax.lax.scan(
        token, jnp.zeros(u.shape[:1] + a.shape, F32),
        jax.tree.map(lambda t: jnp.moveaxis(t, 1, 0), (u, step, b_mat, c_mat)),
    )
    y = jnp.moveaxis(y, 0, 1) + p["d_skip"] * u
    return (y * jax.nn.silu(z)) @ p["w_out"]


def _plain_attention(h, attn, sizes, q_block):
    b, s, _ = h.shape
    hd = sizes["d_head"]
    q = (h @ attn["wq"]).reshape(b, s, sizes["n_head"], hd)
    k = (h @ attn["wk"]).reshape(b, s, sizes["n_kv_head"], hd)
    v = (h @ attn["wv"]).reshape(b, s, sizes["n_kv_head"], hd)
    return _attention(q, k, v, 0, q_block) @ attn["wo"]


def _part(stacks, name, k):
    """The k-th part of a kind, float32: its stacks ``name``,
    ``name.1``, ... end to end."""
    stack, more = name, 0
    while True:
        n = jax.tree.leaves(stacks[stack])[0].shape[0]
        if k < n:
            return _f32(jax.tree.map(lambda t: t[k], stacks[stack]))
        k, more = k - n, more + 1
        stack = f"{name}.{more}"


def forward(params, tokens, sizes, q_block=1024):
    """tokens [B, S] int32 -> logits [B, S, vocab] float32."""
    embed = params["embed"]["tokens"]
    stacks = params["layers"]
    x = jnp.take(embed, tokens, axis=0).astype(F32)
    seen = {"m": 0, "*": 0}
    for i, kind in enumerate(_layers(sizes)):
        name = {"m": "mamba1", "*": "attention"}[kind]
        mixer = _part(stacks, name, seen[kind])
        seen[kind] += 1
        h = _rms(x, mixer["ln"], sizes)
        if kind == "m":
            x = x + _mamba(h, mixer["ssm1"], sizes)
        else:
            x = x + _plain_attention(h, mixer["attn"], sizes, q_block)
        part = _part(stacks, "mlp", i)
        h, mlp = _rms(x, part["ln"], sizes), part["mlp"]
        x = x + (
            jax.nn.silu(h @ mlp["w_gate"]) * (h @ mlp["w_up"])
        ) @ mlp["w_down"]
    x = _rms(x, _f32(params["final_norm"]), sizes)
    return x @ embed.astype(F32).T


def loss_and_logits(params, batch, sizes, q_block=1024):
    """Mean next-token cross-entropy over every position of
    ``batch["tokens"]`` against ``batch["targets"]``, and the logits."""
    logits = forward(params, batch["tokens"], sizes, q_block)
    logz = jax.nn.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, batch["targets"][..., None], -1)[..., 0]
    return jnp.mean(logz - tgt), logits
