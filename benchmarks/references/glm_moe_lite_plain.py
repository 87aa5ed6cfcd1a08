"""Plain reference for GLM-4.7-Flash (``model_type: glm4_moe_lite``;
https://huggingface.co/zai-org/GLM-4.7-Flash/blob/main/config.json): the
forward pass, the mean next-token loss and the multi-token-prediction
term in straightforward ``jax.numpy`` and float32. No kernel, no sort,
no ``ragged_dot``, no capacity, no drop, no remat. The caller runs it
under ``jax.default_matmul_precision("highest")``.

It reads the program's parameter tree by name (``dense_layers`` and
``layers`` stacked on axis 0, ``mtp`` one block) and the configuration
file's ``sizes``. With x the residual stream [B, S, d], rms an RMSNorm
with a learned scale, and H heads:

Every layer, latent attention (MLA, DeepSeek-V2 section 2.1) on h =
rms(x; ln1):

    c_q = rms(h W_dq; q_a_norm)            (rank q_lora_rank)
    q_h = c_q W_uq,h = [q_nope,h | q_rope,h]  (qk_nope | qk_rope channels)
    [c_kv | k_r] = h W_dkv                 (rank kv_lora_rank | qk_rope)
    c_kv = rms(c_kv; kv_a_norm)
    [k_nope,h | v_h] = c_kv W_ukv,h        (qk_nope | v_head_dim)
    q_h = [q_nope,h | rope(q_rope,h)];  k_h = [k_nope,h | rope(k_r)]
        (k_r is ONE set of rope channels, the same for every head)
    a_h = causal_softmax(q_h k_h^T / sqrt(qk_nope + qk_rope)) v_h
    x = x + concat_h(a_h) W_o

then on g = rms(x; ln2), in the first ``n_dense_layer`` layers

    x = x + (silu(g W_gate) * (g W_up)) W_down        (width d_ff)

and in every later layer the routed block (``topk_method: noaux_tc``,
``n_group = topk_group = 1``: no group limit)

    l = g W_r  (float32, n_experts wide);  s = sigmoid(l)
    e_1..k = the k experts with the largest s (free-running), or the
             ids handed in (teacher-forced)
    w_j = routed_scaling_factor * s[e_j] / sum_j s[e_j]
          (``norm_topk_prob``: over ALL k chosen, held here or not)
    x = x + sum_{j: e_j held here} w_j E_{e_j}(g) + E_shared(g)
    E(g) = (silu(g W_g) * (g W_u)) W_d   (width d_expert; the shared
           one n_shared_experts * d_expert)

The chip holds experts ``[expert_offset, expert_offset +
n_experts_held)`` of the router's ``n_experts``: what the others would
have added is left out, here as in the program, and that partial sum is
what goes on. Then the final RMSNorm and the untied head over the
vocabulary held here.

The multi-token-prediction module (DeepSeek-V3 section 2.2, one depth),
with h_i the trunk's output BEFORE the final norm and t the tokens:

    z_i = W_eh [rms(Emb(t_{i+1}); enorm) | rms(h_i; hnorm)]
    one layer as above (attention, then the routed block)
    m_i = rms(z_i; mtp.norm) W_head         (the trunk's Emb and head)
    mtp_loss = mtp_loss_coef * mean_{i < S-1} CE(m_i, t_{i+2})

t_{i+1} is ``tokens`` one place on and t_{i+2} ``targets`` one place on;
the last position has neither and is left out of the mean.

Every held expert runs over every token, one after another, its output
scaled by the token's weight for it (zero where the token did not
choose it). Under teacher forcing the ids are the ONLY thing taken from
the program.

Departures from the published model, each so that program and reference
can agree, each listed in the configuration file:

- the ``noaux_tc`` selection bias (``e_score_correction_bias``) is a
  buffer without gradient that starts at zero and is moved by a rule
  outside the loss: held at zero, so it is in neither;
- rope pairs channel i with i + qk_rope/2 (rotate-half), positions
  0..S-1, no scaling (``rope_scaling: null``);
- ``rms_norm_eps`` arrives as ``sizes["norm_eps"]`` (the program fixes
  1e-6 where the model publishes 1e-5);
- ``mtp_loss_coef`` and the order of the concatenation have no key in
  ``config.json``.
"""

import jax
import jax.numpy as jnp

from benchmarks.lib import flops
from benchmarks.references.decoder_plain import F32, _attention, _norm, _rope

# Terms of the objective that are teacher-forced cross-entropies, held
# to LOSS_TOL (2e-4) and not to ROUTER_LOSS_TOL (lib/routed.py). The
# readings that put ``mtp_loss`` here (PERF.md section 7 (b); my chip
# runs, PR 34, 13 seeds at the cell's size): sound 6.9e-7..2.1e-5, a
# tenth of the limit; its weight 1% off 1.0e-2; its targets one place
# further on 2.7e-4..2.3e-3 (with seeded weights and uniform tokens a
# prediction knows nothing of its target, so that control is sampling
# noise: it fails LOSS_TOL on all 13 seeds, ROUTER_LOSS_TOL on 2)
CROSS_ENTROPY_TERMS = ("mtp_loss",)


def required_terms(sizes, seq):
    """The two terms of ``lib/flops.py``'s convention for this
    architecture on this chip: layers counted kind by kind, a chip that
    holds h of E experts counting k * h / E of them a token, the shared
    expert whole, the module's projection, block and the head once
    more, the vocabulary as sliced."""
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
    met = (
        sizes["expert_top_k"] * sizes["n_experts_held"] / sizes["n_experts"]
        + sizes["n_shared_experts"]
    )
    routed = attn + d * sizes["n_experts"] + met * expert
    dense = attn + 3 * d * sizes["d_ff"]
    head = d * sizes["vocab_size"]
    n_routed = sizes["n_layer"] - sizes["n_dense_layer"]
    mtp = sizes["n_mtp_module"] * (2 * d * d + routed + head)
    attn_layers = sizes["n_layer"] + sizes["n_mtp_module"]
    return {
        "multiplied_params": int(
            sizes["n_dense_layer"] * dense + n_routed * routed + mtp + head
        ),
        "attention_pair_channels": (
            attn_layers * h * (qk + sizes["v_head_dim"]) / 2
            * flops.mean_span(seq, sizes.get("attn_window", 0))
        ),
    }


def _f32(tree):
    return jax.tree.map(lambda w: w.astype(F32), tree)


def _rms(x, p, sizes):
    return _norm(x, p, "rmsnorm", sizes["norm_eps"])


def _swiglu(g, w_gate, w_up, w_down):
    return (jax.nn.silu(g @ w_gate.astype(F32)) * (g @ w_up.astype(F32))) \
        @ w_down.astype(F32)


def _latent_attention(h, attn, sizes, q_block):
    b, s, _ = h.shape
    nh, rkv = sizes["n_head"], sizes["kv_lora_rank"]
    nope, rd = sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"]
    theta = sizes["rope_theta"]
    c_q = _rms(h @ attn["wq_a"], attn["q_a_norm"], sizes)
    q = (c_q @ attn["wq_b"]).reshape(b, s, nh, nope + rd)
    down = h @ attn["wkv_a"]
    c_kv = _rms(down[..., :rkv], attn["kv_a_norm"], sizes)
    k_r = _rope(down[..., rkv:].reshape(b, s, 1, rd), theta)
    up = (c_kv @ attn["wkv_b"]).reshape(b, s, nh, -1)
    k_nope, v = up[..., :nope], up[..., nope:]
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], theta)], -1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_r, (b, s, nh, rd))], -1)
    return _attention(q, k, v, 0, q_block) @ attn["wo"]


def _routed(g, moe, sizes, ids):
    """g [T, d], ids [T, k] or None -> (this chip's part of the block's
    output [T, d], router logits [T, E])."""
    k = sizes["expert_top_k"]
    first, held = sizes["expert_offset"], sizes["n_experts_held"]
    logits = g @ moe["w_gate"].astype(F32)
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
        return total + _swiglu(g, w_g, w_u, w_d) * w_tok[:, None], None

    out, _ = jax.lax.scan(
        expert, jnp.zeros_like(g),
        (moe["w_gate_proj"], moe["w_up"], moe["w_down"], weight.T),
    )
    shared = moe["shared"]
    out = out + _swiglu(g, shared["w_gate"], shared["w_up"], shared["w_down"])
    return out, logits


def _layer(x, p, sizes, q_block, ids=None):
    """One pre-norm residual layer; routed where it holds ``moe``.
    Returns (x, router logits [B, S, E] or None)."""
    b, s, d = x.shape
    x = x + _latent_attention(
        _rms(x, _f32(p["ln1"]), sizes), _f32(p["attn"]), sizes, q_block
    )
    g = _rms(x, _f32(p["ln2"]), sizes)
    if "moe" not in p:
        mlp = p["mlp"]
        return x + _swiglu(g, mlp["w_gate"], mlp["w_up"], mlp["w_down"]), None
    # the experts are cast one at a time, inside
    out, logits = _routed(
        g.reshape(b * s, d), p["moe"], sizes,
        None if ids is None else ids.reshape(b * s, -1),
    )
    return x + out.reshape(b, s, d), logits.reshape(b, s, -1)


def _next(t):
    """[B, S] one place on; the last position repeats (and is masked)."""
    return jnp.concatenate([t[:, 1:], t[:, -1:]], axis=1)


def forward(params, tokens, sizes, q_block=1024, choices=None):
    """tokens [B, S] -> (logits [B, S, vocab], the module's logits
    [B, S, vocab], router logits [routed layers + 1, B, S, E], the
    module's row last). ``choices`` int32 of that leading shape forces
    the routing."""
    embed = params["embed"]["tokens"]
    head = params["lm_head"]["w"].astype(F32)
    x = jnp.take(embed, tokens, axis=0).astype(F32)

    x, _ = jax.lax.scan(
        lambda x, p: (_layer(x, p, sizes, q_block)[0], None),
        x, params["dense_layers"],
    )
    n_routed = sizes["n_layer"] - sizes["n_dense_layer"]
    trunk_ids = None if choices is None else choices[:n_routed]
    x, trunk_logits = jax.lax.scan(
        lambda x, inp: _layer(x, inp[0], sizes, q_block, inp[1]),
        x, (params["layers"], trunk_ids),
    )
    logits = _rms(x, _f32(params["final_norm"]), sizes) @ head

    m = params["mtp"]
    e = jnp.take(embed, _next(tokens), axis=0).astype(F32)
    z = jnp.concatenate(
        [_rms(e, _f32(m["enorm"]), sizes), _rms(x, _f32(m["hnorm"]), sizes)],
        axis=-1,
    ) @ m["eh_proj"].astype(F32)
    z, m_router = _layer(
        z, m["block"], sizes, q_block,
        None if choices is None else choices[n_routed],
    )
    m_logits = _rms(z, _f32(m["norm"]), sizes) @ head
    router_logits = jnp.concatenate([trunk_logits, m_router[None]], axis=0)
    return logits, m_logits, router_logits


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
