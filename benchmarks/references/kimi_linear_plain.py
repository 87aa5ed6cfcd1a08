"""Plain reference for Kimi-Linear-48B-A3B-Instruct (``model_type:
kimi_linear``; https://huggingface.co/moonshotai/Kimi-Linear-48B-A3B-
Instruct/blob/main/config.json; Kimi Linear, arXiv:2510.26692): the
forward pass and the mean next-token loss in straightforward
``jax.numpy`` and float32. No kernel, no chunk, no sort, no
``ragged_dot``, no capacity, no drop, no remat, and no import from the
program. The caller runs it under
``jax.default_matmul_precision("highest")``.

It reads the program's parameter tree by name (``layers`` holding one
stack a kind of part: ``kda``, ``attention``, ``mlp``, ``experts``) and
the configuration file's ``sizes``. A layer is TWO parts,
``sizes["layer_pattern"]`` names them (``K-`` the first layer, ``Ke`` a
linear layer, ``*e`` a full one), pre-norm twice:

    x <- x + mixer(rms(x; ln));  x <- x + mlp(rms(x; ln))

with ``rms(x; w) = x / sqrt(mean(x^2) + eps) * w``, eps
``sizes["norm_eps"]`` (1e-5).

``K``, a KDA mixer: H heads of D key and D value channels, each head
its own q, k and v, on u = rms(x):

    [q | k | v] = u W_qkv;  [f | z | b] = u W_gates
    [q | k | v]_t <- silu( sum_{j<K} c_j * [q | k | v]_{t-K+1+j} )
              (depthwise, causal, no bias; f, z, b are not convolved)
    beta_t,h = sigmoid(b_t,h)
    g_t,h,d = -exp(A_log_h) softplus((f_t W_fb)_h,d + dt_bias_h,d)
              (ONE DECAY A HEAD AND KEY CHANNEL, through a low rank)
    q, k <- q / |q|, k / |k| a head (eps 1e-6 under the root)
    q <- q / sqrt(D)
    S' = Diag(exp(g_t,h)) S_{t-1,h}                  (D x D; S_-1 = 0;
              row d of S, key channel d, decays by exp(g_t,h,d))
    S_t,h = S' + beta_t,h k_t (v_t,h - S'^T k_t)^T
    o_t,h = S_t,h^T q_t
    y_t,h = o_t,h / rms(o_t,h) * w_norm * sigmoid((z_t W_gb)_h)
              (ONE w_norm of D; the norm BEFORE the gate; a SIGMOID)
    out_t = y_t W_out

The recurrence is run AS WRITTEN, token by token (``lax.scan`` over t):
the program's chunked form (sub-blocks, a triangular inverse a chunk)
is another algorithm for the same numbers.

``*``, latent attention WITHOUT positions (``mla_use_nope``), n_head
heads, q at full rank (``q_lora_rank`` null):

    q_h = (u W_q)_h                               (nope + rope channels)
    [c | k_r] = u W_kva;  c <- rms(c; w_c)
    [k_n,h | v_h] = (c W_kvb)_h       (nope key and v_head_dim value
              channels a head)
    k_h = [k_n,h | k_r]       (k_r shared by the heads; NOT rotated, nor
              are q's last channels)
    o_t,h = sum_{s<=t} softmax_s(q_t,h . k_s,h / sqrt(nope + rope)) v_s,h
    out_t = o_t W_o

computed a block of queries at a time so that 16,384 tokens fit.

``-``, the first layer's dense SwiGLU of ``d_ff``. ``e``, the routed
block:

    l = u W_r  (float32, n_experts wide);  s = sigmoid(l)
    e_1..k = the k experts with the largest s (free-running; ONE group,
             so ``use_grouped_topk`` is a top-k over all), or the ids
             handed in (teacher-forced)
    w_j = routed_scaling_factor * s[e_j] / sum_j s[e_j]
             (``moe_renormalize``: over ALL k chosen, held here or not)
    out = sum_{j: e_j held here} w_j swiglu_{e_j}(u) + swiglu_shared(u)

The chip holds experts ``[expert_offset, expert_offset +
n_experts_held)`` of the router's ``n_experts``: what the others would
have added is left out, here as in the program, and that partial sum is
what goes on. Then the final RMSNorm and the untied head over the
vocabulary held here. Every held expert runs over every token, one
after another, its output scaled by the token's weight for it (zero
where the token did not choose it). Under teacher forcing the ids are
the ONLY thing taken from the program.

Departures from the published model, each listed in the configuration
file: ``W_q``, ``W_k``, ``W_v`` side by side in one ``w_qkv`` and the
three narrow inputs in one ``w_gates`` (concatenations of columns: the
same function); the selection bias of the router (a buffer, zero at
initialisation) held at zero, so in neither program nor reference; no
router loss (``config.json`` carries no coefficient).

``required_terms`` counts, beside the matrices, the recurrence's own
work as ``qwen3_next_plain`` does: 3.5 x D x D multiply-adds a token and
head, entered as that many MULTIPLIED PARAMETERS; and the latent
layer's pairs at the MEAN of its score and value channels, (192 + 128)
/ 2, since a pair costs one product over each. The conv's taps are not
counted, nor the L2 norms.
"""

import jax
import jax.numpy as jnp

from benchmarks.lib import flops

F32 = jnp.float32
PART_NAMES = {"K": "kda", "*": "attention", "-": "mlp", "e": "experts"}


def kda_multiply_adds(sizes):
    """Multiply-adds the recurrence itself costs a token and layer."""
    return int(3.5 * sizes["kda_heads"] * sizes["kda_head_dim"] ** 2)


def score_channels(sizes):
    return sizes["qk_nope_head_dim"] + sizes["qk_rope_head_dim"]


def required_terms(sizes, seq):
    """The two terms of ``lib/flops.py``'s convention for this
    architecture on this chip: parts counted kind by kind, a chip that
    holds h of E experts counting k * h / E of them a token, the shared
    expert whole, the recurrence as the docstring says, the latent
    layer's pairs at the mean of score and value channels, the
    vocabulary as sliced."""
    d, nh = sizes["d_model"], sizes["n_head"]
    inner = sizes["kda_heads"] * sizes["kda_head_dim"]
    rank = sizes["kda_gate_rank"]
    nope, vd = sizes["qk_nope_head_dim"], sizes["v_head_dim"]
    met = sizes["expert_top_k"] * sizes["n_experts_held"] / sizes["n_experts"]
    part = {
        "K": (
            d * (3 * inner + 2 * rank + sizes["kda_heads"])
            + 2 * rank * inner + inner * d + kda_multiply_adds(sizes)
        ),
        "*": (
            d * nh * score_channels(sizes)
            + d * (sizes["kv_lora_rank"] + sizes["qk_rope_head_dim"])
            + sizes["kv_lora_rank"] * nh * (nope + vd) + nh * vd * d
        ),
        "-": 3 * d * sizes["d_ff"],
        "e": (
            d * sizes["n_experts"]
            + 3 * d * sizes["n_shared_experts"] * sizes["d_expert"]
            + met * 3 * d * sizes["d_expert"]
        ),
    }
    pattern = sizes["layer_pattern"]
    return {
        "multiplied_params": int(
            sum(part[c] for c in pattern) + d * sizes["vocab_size"]
        ),
        "attention_pair_channels": (
            pattern.count("*") * nh * (score_channels(sizes) + vd) / 2
            * flops.mean_span(seq)
        ),
    }


def _f32(tree):
    return jax.tree.map(lambda w: w.astype(F32), tree)


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)


def _norm(x, p, sizes):
    return _rms(x, sizes["norm_eps"]) * p["scale"].astype(F32)


def _l2(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


def _kda(u, kda, sizes):
    """u [B, S, d] -> (the mixer's output [B, S, d], the mean square of
    the read-out before its norm)."""
    b, s, _ = u.shape
    heads, dh = sizes["kda_heads"], sizes["kda_head_dim"]
    rank, taps = sizes["kda_gate_rank"], sizes["conv_kernel"]
    inner = heads * dh
    qkv = u @ kda["w_qkv"]
    f, z, beta = jnp.split(u @ kda["w_gates"], [rank, 2 * rank], axis=-1)
    padded = jnp.pad(qkv, ((0, 0), (taps - 1, 0), (0, 0)))
    qkv = jax.nn.silu(
        sum(padded[:, j:j + s] * kda["conv_w"][j] for j in range(taps))
    )
    q, k, v = (t.reshape(b, s, heads, dh) for t in jnp.split(qkv, 3, -1))
    q, k = _l2(q) * dh ** -0.5, _l2(k)
    beta = jax.nn.sigmoid(beta)
    decay = jnp.exp(
        -jnp.exp(kda["a_log"])[:, None]
        * jax.nn.softplus(f @ kda["w_fb"] + kda["dt_bias"]).reshape(
            b, s, heads, dh
        )
    )                                                  # [B, S, H, D]: a channel

    def token(state, inp):
        q_t, k_t, v_t, beta_t, decay_t = inp
        state = decay_t[..., None] * state             # row d by its own
        seen = jnp.einsum("bhde,bhd->bhe", state, k_t)
        state = state + (beta_t[..., None] * k_t)[..., None] * (
            (v_t - seen)[:, :, None, :]
        )
        return state, jnp.einsum("bhde,bhd->bhe", state, q_t)

    _, o = jax.lax.scan(
        token, jnp.zeros((b, heads, dh, dh), F32),
        jax.tree.map(
            lambda t: jnp.moveaxis(t, 1, 0), (q, k, v, beta, decay)
        ),
    )
    o = jnp.moveaxis(o, 0, 1)                          # [B, S, H, D]
    y = _rms(o, sizes["norm_eps"]) * kda["norm"]["scale"]
    y = y.reshape(b, s, inner) * jax.nn.sigmoid(z @ kda["w_gb"])
    return y @ kda["w_out"], jnp.mean(o * o)


def _causal_attention(q, k, v, q_block):
    """q, k [B, S, H, Dqk], v [B, S, H, Dv] -> [B, S, H * Dv]: a plain
    softmax over the visible keys, a block of queries at a time."""
    b, s, h, d = q.shape
    q_block = min(q_block, s)
    if s % q_block:
        raise ValueError(f"sequence {s} is not a multiple of {q_block}")
    kpos = jnp.arange(s)[None, :]

    def rows(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, q_block, axis=1)
        qpos = start + jnp.arange(q_block)[:, None]
        scores = jnp.einsum("bqhd,bkhd->bhqk", qb, k) * d ** -0.5
        scores = jnp.where((kpos <= qpos)[None, None], scores, -jnp.inf)
        return jnp.einsum("bhqk,bkhe->bqhe", jax.nn.softmax(scores, -1), v)

    out = jax.lax.map(rows, jnp.arange(0, s, q_block))  # [nb, B, qb, H, Dv]
    return jnp.moveaxis(out, 0, 1).reshape(b, s, h * v.shape[-1])


def _latent_attention(u, attn, sizes, q_block):
    b, s, _ = u.shape
    nh, rank = sizes["n_head"], sizes["kv_lora_rank"]
    nope, rope = sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"]
    q = (u @ attn["wq"]).reshape(b, s, nh, nope + rope)
    c, k_r = jnp.split(u @ attn["wkv_a"], [rank], axis=-1)
    c = _rms(c, sizes["norm_eps"]) * attn["kv_a_norm"]["scale"]
    k_n, v = jnp.split(
        (c @ attn["wkv_b"]).reshape(b, s, nh, -1), [nope], axis=-1
    )
    # no rotation anywhere: mla_use_nope
    k = jnp.concatenate(
        [k_n, jnp.broadcast_to(k_r[:, :, None, :], (b, s, nh, rope))], -1
    )
    return _causal_attention(q, k, v, q_block) @ attn["wo"]


def _swiglu(u, w_gate, w_up, w_down):
    return (jax.nn.silu(u @ w_gate.astype(F32)) * (u @ w_up.astype(F32))) \
        @ w_down.astype(F32)


def _routed(u, moe, sizes, ids):
    """u [T, d], ids [T, k] or None -> (this chip's part of the block's
    output [T, d], router logits [T, E])."""
    k = sizes["expert_top_k"]
    first, held = sizes["expert_offset"], sizes["n_experts_held"]
    logits = u @ moe["w_gate"].astype(F32)
    score = jax.nn.sigmoid(logits)
    if ids is None:
        ids = jax.lax.top_k(score, k)[1]
    top = jnp.take_along_axis(score, ids, axis=-1)
    if sizes["moe_renorm_topk"]:
        top = top / jnp.sum(top, -1, keepdims=True)
    top = sizes["routed_scaling_factor"] * top
    here = first + jnp.arange(held)
    # a token's weight for each expert held here: 0 where not chosen
    weight = jnp.sum(
        jnp.where(ids[:, :, None] == here, top[:, :, None], 0.0), axis=1
    )

    def expert(total, args):
        w_g, w_u, w_d, w_tok = args
        return total + _swiglu(u, w_g, w_u, w_d) * w_tok[:, None], None

    out, _ = jax.lax.scan(
        expert, jnp.zeros_like(u),
        (moe["w_gate_proj"], moe["w_up"], moe["w_down"], weight.T),
    )
    shared = moe["shared"]
    return out + _swiglu(
        u, shared["w_gate"], shared["w_up"], shared["w_down"]
    ), logits


def _part(x, letter, p, sizes, q_block, ids=None):
    """One part. Returns (x, router logits [B, S, E] or None, the
    read-out's mean square or None)."""
    b, s, d = x.shape
    u = _norm(x, p["ln"], sizes)
    if letter == "K":
        out, readout = _kda(u, _f32(p["kda"]), sizes)
        return x + out, None, readout
    if letter == "*":
        out = _latent_attention(u, _f32(p["attn"]), sizes, q_block)
        return x + out, None, None
    if letter == "-":
        mlp = p["mlp"]
        return x + _swiglu(
            u, mlp["w_gate"], mlp["w_up"], mlp["w_down"]
        ), None, None
    # the experts are cast one at a time, inside
    out, logits = _routed(
        u.reshape(b * s, d), p["moe"], sizes,
        None if ids is None else ids.reshape(b * s, -1),
    )
    return x + out.reshape(b, s, d), logits.reshape(b, s, -1), None


def forward(params, tokens, sizes, q_block=1024, choices=None):
    """tokens [B, S] -> (logits [B, S, vocab] float32, router logits
    [routed blocks, B, S, E] before the sigmoid, which is monotone: the
    top-k is the same; the read-outs' mean square, mean over the KDA
    layers). ``choices`` int32 [routed blocks, B, S, k] forces the
    routing."""
    x = jnp.take(params["embed"]["tokens"], tokens, axis=0).astype(F32)
    seen = dict.fromkeys(PART_NAMES, 0)
    router, readouts = [], []
    for letter in sizes["layer_pattern"]:
        stack = params["layers"][PART_NAMES[letter]]
        p = jax.tree.map(lambda t: t[seen[letter]], stack)
        seen[letter] += 1
        ids = None
        if letter == "e" and choices is not None:
            ids = choices[len(router)]
        x, logits, readout = _part(x, letter, p, sizes, q_block, ids)
        if logits is not None:
            router.append(logits)
        if readout is not None:
            readouts.append(readout)
    x = _norm(x, params["final_norm"], sizes)
    return (
        x @ params["lm_head"]["w"].astype(F32), jnp.stack(router),
        jnp.mean(jnp.stack(readouts)),
    )


def _mean_ce(logits, targets):
    logz = jax.nn.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, targets[..., None], -1)[..., 0]
    return jnp.mean(logz - tgt)


def loss_and_logits(params, batch, sizes, q_block=1024):
    """Free-running: the reference's own top-k. Mean next-token
    cross-entropy (no other term) and the logits."""
    logits, _, _ = forward(params, batch["tokens"], sizes, q_block)
    return _mean_ce(logits, batch["targets"]), logits


def loss_and_logits_routed(params, batch, sizes, q_block, choices):
    """Teacher-forced: every token goes to the experts ``choices``
    names. Mean cross-entropy, logits, and ``routed``: the router logits
    per routed block and, under the program's step metric's name, the
    read-outs' mean square — a term ``lib/routed`` holds at
    ROUTER_LOSS_TOL, since a uniform scale of o (q's 1 / sqrt(D)) hides
    from the logits behind the norm a head."""
    logits, router_logits, readout = forward(
        params, batch["tokens"], sizes, q_block, choices
    )
    routed = {"router_logits": router_logits, "kda_readout_ms": readout}
    return _mean_ce(logits, batch["targets"]), logits, routed
