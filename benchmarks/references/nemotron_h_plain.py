"""Plain reference for NVIDIA-Nemotron-3-Super-120B-A12B (``model_type:
nemotron_h``; https://huggingface.co/nvidia/
NVIDIA-Nemotron-3-Super-120B-A12B-BF16/blob/main/config.json): the
forward pass, the mean next-token loss and the multi-token-prediction
term in straightforward ``jax.numpy`` and float32. No kernel, no chunk,
no sort, no ``ragged_dot``, no capacity, no drop, no remat. The caller
runs it under ``jax.default_matmul_precision("highest")``.

It reads the program's parameter tree by name (``layers`` holding one
stack a kind of layer: ``mamba``, ``attention``, ``experts``; ``mtp``
the module) and the configuration file's ``sizes``. Every layer is ONE
part, named by ``sizes["layer_pattern"]`` (``hybrid_override_pattern``):

    x <- x + part(rms(x; ln))

``M``, a Mamba-2 mixer (H heads of P channels, G groups that share B
and C of N channels, head h in group g(h) = h // (H / G)), on u =
rms(x):

    [z | xBC | dt] = u W_in
    xBC_t <- silu( sum_{j<K} c_j * xBC_{t-K+1+j} + b )   (depthwise,
              causal: zeros before the first token);  xBC = [x | B | C]
    D_t,h = softplus(dt_t,h + dt_bias_h);  a_t,h = exp(-exp(A_log_h) D_t,h)
    S_t,h = a_t,h S_{t-1,h} + D_t,h x_t,h B_t,g(h)^T      (P x N; S_-1 = 0)
    y_t,h = S_t,h C_t,g(h) + D_h x_t,h
    out_t = ( rms over each of G groups of (y_t * silu(z_t)) * gamma ) W_out

The recurrence is run AS WRITTEN, token by token (``lax.scan`` over t):
the program's chunked form is another algorithm for the same numbers.

``*``, an attention of n_head query and n_kv_head key-value heads, no
bias, NO rope and no other position signal (NemotronH's attention
layers apply none):

    o_t,h = sum_{s<=t} softmax_s(q_t,h . k_s,g(h) / sqrt(head)) v_s,g(h)
    out_t = o_t W_o

``E``, a LatentMoE block (``n_group = topk_group = 1``: no group limit):

    l = u W_r (float32, n_experts wide);  s = sigmoid(l)
    e_1..k = the k experts with the largest s (free-running), or the
             ids handed in (teacher-forced)
    w_j = routed_scaling_factor * s[e_j] / sum_j s[e_j]
          (``norm_topk_prob``: over ALL k chosen, held here or not)
    lat = u W_down                          (d_model -> moe_latent_size)
    f_e(lat) = relu(lat W1_e)^2 W2_e        (latent -> d_expert -> latent)
    out = ( sum_{j: e_j held here} w_j f_{e_j}(lat) ) W_up
          + relu(u V1)^2 V2                 (the shared expert, on u)

The chip holds experts ``[expert_offset, expert_offset +
n_experts_held)`` of the router's ``n_experts``: what the others would
have added is left out, here as in the program, and that partial sum
(through W_up, which is linear) is what goes on. Then the final RMSNorm
and the untied head over the vocabulary held here.

The multi-token-prediction module (DeepSeek-V3 section 2.2, one depth),
with h_i the trunk's output BEFORE the final norm and t the tokens:

    z_i = W_eh [rms(Emb(t_{i+1}); enorm) | rms(h_i; hnorm)]
    the layers ``sizes["mtp_pattern"]`` names (``*E``), as above
    m_i = rms(z_i; mtp.norm) W_head         (the trunk's Emb and head)
    mtp_loss = mtp_loss_coef * mean_{i < S-1} CE(m_i, t_{i+2})

Every held expert runs over every token, one after another, its output
scaled by the token's weight for it (zero where the token did not
choose it). Under teacher forcing the ids are the ONLY thing taken from
the program.

Departures from the published model, each so that program and reference
can agree, each listed in the configuration file:

- the selection bias (``e_score_correction_bias``) is a buffer without
  gradient that starts at zero: held at zero, so it is in neither;
- the trunk's RMSNorms take ``sizes["norm_eps"]`` (the program fixes
  1e-6 where the model publishes 1e-5); the mixer's gated group norm
  takes the published 1e-5 (``sizes["ssm_norm_eps"]``);
- ``time_step_limit`` is (0, inf): the time step is not clamped;
- ``mtp_loss_coef``, the order of the module's concatenation, sigmoid
  scoring and the latent's placement have no key in ``config.json``.

``required_terms`` counts, beside the matrices, the recurrence's own
work: the state update and the read-out are 2 * H * P * N multiply-adds
a token a layer (2.10 M at the published sizes, 0.9% of the total).
They are entered as that many MULTIPLIED PARAMETERS, each of which
stands for one multiply-add forward and two backward, which is what a
multiply-add of the recurrence costs too. A clause of its own in
``lib/flops.py`` is a later ``benchmark`` issue's. The conv's K taps a
channel (41 k multiply-adds a token) are not counted.
"""

import jax
import jax.numpy as jnp

from benchmarks.lib import flops
from benchmarks.references.decoder_plain import F32, _attention, _norm

# ``mtp_loss`` is a teacher-forced cross-entropy, held to LOSS_TOL as
# GLM-4.7-Flash's is (glm_moe_lite_plain.py has the readings behind it)
CROSS_ENTROPY_TERMS = ("mtp_loss",)

PART_NAMES = {"M": "mamba", "*": "attention", "E": "experts"}


def required_terms(sizes, seq):
    """The two terms of ``lib/flops.py``'s convention for this
    architecture on this chip: layers counted kind by kind, a chip that
    holds h of E experts counting k * h / E of them a token, the shared
    expert and both latent projections whole, the scan's recurrence as
    the docstring says, the module's projection, layers and the head
    once more, the vocabulary as sliced."""
    d = sizes["d_model"]
    heads, p, n = (
        sizes["mamba_num_heads"], sizes["mamba_head_dim"],
        sizes["ssm_state_size"],
    )
    inner = heads * p
    w_in = d * (2 * inner + 2 * sizes["n_groups"] * n + heads)
    d_attn = sizes["n_head"] * sizes["d_head"]
    latent = sizes["moe_latent_size"]
    met = sizes["expert_top_k"] * sizes["n_experts_held"] / sizes["n_experts"]
    part = {
        "M": w_in + inner * d + 2 * inner * n,
        "*": 2 * d * d_attn + 2 * d * sizes["n_kv_head"] * sizes["d_head"],
        "E": (
            d * sizes["n_experts"] + 2 * d * latent
            + 2 * d * sizes["d_shared_expert"]
            + met * 2 * latent * sizes["d_expert"]
        ),
    }
    head = d * sizes["vocab_size"]
    trunk, module = sizes["layer_pattern"], sizes["mtp_pattern"]
    return {
        "multiplied_params": int(
            sum(part[c] for c in trunk) + head
            + 2 * d * d + sum(part[c] for c in module) + head
        ),
        "attention_pair_channels": (
            (trunk + module).count("*") * d_attn * flops.mean_span(seq)
        ),
    }


def _f32(tree):
    return jax.tree.map(lambda w: w.astype(F32), tree)


def _rms(x, p, sizes):
    return _norm(x, p, "rmsnorm", sizes["norm_eps"])


def _relu2(u, w_up, w_down):
    return jnp.square(jax.nn.relu(u @ w_up.astype(F32))) @ w_down.astype(F32)


def _mamba(u, ssm, sizes):
    """u [B, S, d] -> the mixer's output [B, S, d]."""
    b, s, _ = u.shape
    heads, p, n = (
        sizes["mamba_num_heads"], sizes["mamba_head_dim"],
        sizes["ssm_state_size"],
    )
    groups, taps = sizes["n_groups"], sizes["conv_kernel"]
    inner = heads * p
    proj = u @ ssm["w_in"]
    z, xbc, dt = jnp.split(proj, [inner, proj.shape[-1] - heads], axis=-1)
    padded = jnp.pad(xbc, ((0, 0), (taps - 1, 0), (0, 0)))
    xbc = jax.nn.silu(
        sum(padded[:, j:j + s] * ssm["conv_w"][j] for j in range(taps))
        + ssm["conv_b"]
    )
    x, b_mat, c_mat = jnp.split(xbc, [inner, inner + groups * n], axis=-1)
    x = x.reshape(b, s, heads, p)
    # every head its group's B and C
    b_mat = jnp.repeat(b_mat.reshape(b, s, groups, n), heads // groups, 2)
    c_mat = jnp.repeat(c_mat.reshape(b, s, groups, n), heads // groups, 2)
    step = jax.nn.softplus(dt + ssm["dt_bias"])               # [B, S, H]
    decay = jnp.exp(-jnp.exp(ssm["a_log"]) * step)

    def token(state, inp):
        x_t, b_t, c_t, step_t, decay_t = inp
        state = decay_t[..., None, None] * state + (
            (step_t[..., None] * x_t)[..., None] * b_t[:, :, None, :]
        )
        return state, jnp.einsum("bhpn,bhn->bhp", state, c_t)

    _, y = jax.lax.scan(
        token, jnp.zeros((b, heads, p, n), F32),
        jax.tree.map(
            lambda t: jnp.moveaxis(t, 1, 0), (x, b_mat, c_mat, step, decay)
        ),
    )
    y = jnp.moveaxis(y, 0, 1) + ssm["d_skip"][:, None] * x
    gated = (y.reshape(b, s, inner) * jax.nn.silu(z)).reshape(
        b, s, groups, inner // groups
    )
    gated = gated * jax.lax.rsqrt(
        jnp.mean(gated * gated, -1, keepdims=True) + sizes["ssm_norm_eps"]
    )
    return (gated.reshape(b, s, inner) * ssm["norm"]["scale"]) @ ssm["w_out"]


def _plain_attention(u, attn, sizes, q_block):
    b, s, _ = u.shape
    hd = sizes["d_head"]
    q = (u @ attn["wq"]).reshape(b, s, sizes["n_head"], hd)
    k = (u @ attn["wk"]).reshape(b, s, sizes["n_kv_head"], hd)
    v = (u @ attn["wv"]).reshape(b, s, sizes["n_kv_head"], hd)
    out = _attention(q, k, v, 0, q_block)
    return out.reshape(b, s, -1) @ attn["wo"]


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
    lat = u @ moe["latent"]["w_down"].astype(F32)

    def expert(total, args):
        w_1, w_2, w_tok = args
        return total + _relu2(lat, w_1, w_2) * w_tok[:, None], None

    out, _ = jax.lax.scan(
        expert, jnp.zeros_like(lat), (moe["w_up"], moe["w_down"], weight.T)
    )
    shared = moe["shared"]
    out = out @ moe["latent"]["w_up"].astype(F32) + _relu2(
        u, shared["w_up"], shared["w_down"]
    )
    return out, logits


def _layer(x, letter, p, sizes, q_block, ids=None):
    """One layer of one part. Returns (x, router logits [B, S, E] or
    None)."""
    b, s, d = x.shape
    u = _rms(x, _f32(p["ln"]), sizes)
    if letter == "M":
        return x + _mamba(u, _f32(p["ssm"]), sizes), None
    if letter == "*":
        return x + _plain_attention(u, _f32(p["attn"]), sizes, q_block), None
    # the experts are cast one at a time, inside
    out, logits = _routed(
        u.reshape(b * s, d), p["moe"], sizes,
        None if ids is None else ids.reshape(b * s, -1),
    )
    return x + out.reshape(b, s, d), logits.reshape(b, s, -1)


def _run(x, layers, pattern, sizes, q_block, choices):
    """The layers ``pattern`` names, each the next of its kind's stack.
    ``choices`` [E layers of the pattern, B, S, k] or None. Returns (x,
    the routed layers' router logits, a list)."""
    seen = dict.fromkeys(PART_NAMES, 0)
    router = []
    for letter in pattern:
        stack = layers[PART_NAMES[letter]]
        p = jax.tree.map(lambda t: t[seen[letter]], stack)
        seen[letter] += 1
        ids = None
        if letter == "E" and choices is not None:
            ids = choices[len(router)]
        x, logits = _layer(x, letter, p, sizes, q_block, ids)
        if logits is not None:
            router.append(logits)
    return x, router


def _next(t):
    """[B, S] one place on; the last position repeats (and is masked)."""
    return jnp.concatenate([t[:, 1:], t[:, -1:]], axis=1)


def forward(params, tokens, sizes, q_block=1024, choices=None):
    """tokens [B, S] -> (logits [B, S, vocab], the module's logits
    [B, S, vocab], router logits [routed layers of the trunk, then of
    the module, B, S, E]). ``choices`` int32 of that leading shape
    forces the routing."""
    embed = params["embed"]["tokens"]
    head = params["lm_head"]["w"].astype(F32)
    trunk, module = sizes["layer_pattern"], sizes["mtp_pattern"]
    n_routed = trunk.count("E")
    x = jnp.take(embed, tokens, axis=0).astype(F32)
    x, router = _run(
        x, params["layers"], trunk, sizes, q_block,
        None if choices is None else choices[:n_routed],
    )
    logits = _rms(x, _f32(params["final_norm"]), sizes) @ head

    m = params["mtp"]
    e = jnp.take(embed, _next(tokens), axis=0).astype(F32)
    z = jnp.concatenate(
        [_rms(e, _f32(m["enorm"]), sizes), _rms(x, _f32(m["hnorm"]), sizes)],
        axis=-1,
    ) @ m["eh_proj"].astype(F32)
    z, m_router = _run(
        z, m["block"], module, sizes, q_block,
        None if choices is None else choices[n_routed:],
    )
    m_logits = _rms(z, _f32(m["norm"]), sizes) @ head
    return logits, m_logits, jnp.stack(router + m_router)


def _nll(logits, targets):
    logz = jax.nn.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, targets[..., None], -1)[..., 0]
    return logz - tgt


def _mtp_loss(m_logits, targets, sizes):
    # position i predicts t_{i+2} = targets[i + 1]; the last has none
    nll = _nll(m_logits, _next(targets))[:, :-1]
    return sizes["mtp_loss_coef"] * jnp.mean(nll)


def loss_and_logits(params, batch, sizes, q_block=1024):
    """Free-running: the reference's own top-k. Mean next-token
    cross-entropy (no other term) and the logits."""
    logits, _, _ = forward(params, batch["tokens"], sizes, q_block)
    return jnp.mean(_nll(logits, batch["targets"])), logits


def loss_and_logits_routed(params, batch, sizes, q_block, choices):
    """Teacher-forced: every token goes to the experts ``choices``
    names. Mean cross-entropy, logits, and ``routed``: the router logits
    (before the sigmoid, which is monotone: the top-k is the same) per
    routed layer, the module's last, and the objective's other term."""
    logits, m_logits, router_logits = forward(
        params, batch["tokens"], sizes, q_block, choices
    )
    routed = {
        "router_logits": router_logits,
        "mtp_loss": _mtp_loss(m_logits, batch["targets"], sizes),
    }
    return jnp.mean(_nll(logits, batch["targets"])), logits, routed
