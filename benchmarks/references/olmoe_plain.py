"""Plain reference for OLMoE (``model_type: olmoe``; OLMoE-1B-7B, arXiv
2409.02060, and the published modelling code's ``OlmoeAttention`` and
``OlmoeSparseMoeBlock``): the forward pass and the mean next-token loss
in straightforward ``jax.numpy`` and float32. No sort, no
``ragged_dot``, no kernel, no capacity, no drop. The caller runs it
under ``jax.default_matmul_precision("highest")``.

It reads the program's parameter tree (``decoder.init``'s layout,
per-layer tensors stacked on axis 0) and the configuration file's
``sizes``. Per layer, x the residual stream [B, S, d]:

    h = rms(x; ln1)
    q = rms(h Wq; q_norm);  k = rms(h Wk; k_norm);  v = h Wv
        (the norm runs over the WHOLE projection, all heads at once,
         before the split into heads and before rope)
    a = causal_softmax(rope(q) rope(k)^T / sqrt(head_dim)) v;  x = x + a Wo
    g = rms(x; ln2);  l = g W_r (float32);  p = softmax(l)
    e_1..k = the k experts with the largest p (free-running), or the
             ids handed in (teacher-forced);  w_j = p[e_j], divided by
             their sum only if ``sizes["moe_renorm_topk"]``
             (``norm_topk_prob``; false for OLMoE: raw softmax weights)
    x = x + sum_j w_j (silu(g Wg[e_j]) * (g Wu[e_j])) Wd[e_j]

then the final RMSNorm and the untied head. Every expert runs over
every token, one expert after another, its output scaled by the token's
weight for it (zero where the token did not choose it), so only one
expert's [T, d_ff] intermediate is alive at a time.

Under teacher forcing the ids are the ONLY thing taken from the
program: hidden states, router logits, probabilities, combine weights,
experts and losses are this file's own.

Departures from the published code, each so that program and reference
can agree:

- The balance term is the program's (``parallel/moe.py``), per layer
  and summed over layers: ``moe_lb_loss`` = aux_coef x E x sum_e f_e
  pbar_e with f_e expert e's share of the layer's (token, choice) pairs
  and pbar_e its mean probability. The published helper
  (``load_balancing_loss_func``) concatenates the layers before the
  product and counts per choice slot: k x E x sum_e f_e pbar_e with
  both shares averaged over the layers, the same reward at another
  scale. ``moe_z_loss`` = z_coef x mean(logsumexp(l)^2),
  per layer and summed, as the paper's router z-loss.
- ``rms_norm_eps`` arrives as ``sizes["norm_eps"]``: the program fixes
  its RMSNorm epsilon in code at 1e-6 where OLMoE publishes 1e-5 (the
  configuration file lists it under ``reduced``).
- No bias anywhere and no ``clip_qkv`` (null in the published config).
"""

import jax
import jax.numpy as jnp

from benchmarks.references.decoder_plain import F32, _attention, _norm, _rope


def _f32(tree):
    return jax.tree.map(lambda w: w.astype(F32), tree)


def _mixture(g, moe, sizes, ids):
    """g [T, d], ids [T, k] or None -> (mixture output [T, d], router
    logits [T, E], balance term, z term)."""
    n_exp, k = sizes["n_experts"], sizes["expert_top_k"]
    logits = g @ moe["w_gate"].astype(F32)
    probs = jax.nn.softmax(logits, axis=-1)
    if ids is None:
        ids = jax.lax.top_k(probs, k)[1]
    top_w = jnp.take_along_axis(probs, ids, axis=-1)
    if sizes["moe_renorm_topk"]:
        top_w = top_w / jnp.sum(top_w, -1, keepdims=True)
    chosen = ids[:, :, None] == jnp.arange(n_exp)  # [T, k, E]
    # a token's weight for every expert: 0 where it did not choose it
    weight = jnp.sum(jnp.where(chosen, top_w[:, :, None], 0.0), axis=1)

    def expert(total, args):
        w_g, w_u, w_d, w_tok = args
        y = jax.nn.silu(g @ w_g.astype(F32)) * (g @ w_u.astype(F32))
        return total + (y @ w_d.astype(F32)) * w_tok[:, None], None

    out, _ = jax.lax.scan(
        expert, jnp.zeros_like(g),
        (moe["w_gate_proj"], moe["w_up"], moe["w_down"], weight.T),
    )
    share = jnp.mean(chosen.astype(F32), axis=(0, 1))  # over pairs: sums to 1
    balance = n_exp * jnp.sum(share * jnp.mean(probs, axis=0))
    z = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
    return out, logits, balance, z


def forward(params, tokens, sizes, q_block=1024, choices=None):
    """tokens [B, S] -> (logits [B, S, vocab] float32, routed) with
    ``routed`` the router logits [L, B, S, E] and the two router losses.
    ``choices`` int32 [L, B, S, k] forces the routing."""
    b, s = tokens.shape
    nh, nkv, d = sizes["n_head"], sizes["n_kv_head"], sizes["d_model"]
    hd = d // nh
    eps, theta = sizes["norm_eps"], sizes["rope_theta"]
    x = jnp.take(params["embed"]["tokens"], tokens, axis=0).astype(F32)

    def rms(x, p):
        return _norm(x, p, "rmsnorm", eps)

    def layer(x, inp):
        p, ids = inp
        moe = p["moe"]  # cast expert by expert: 64 experts are 1.6 GB in f32
        ln1, ln2, attn = _f32(p["ln1"]), _f32(p["ln2"]), _f32(p["attn"])
        h = rms(x, ln1)
        q = rms(h @ attn["wq"], attn["q_norm"]).reshape(b, s, nh, hd)
        k = rms(h @ attn["wk"], attn["k_norm"]).reshape(b, s, nkv, hd)
        v = (h @ attn["wv"]).reshape(b, s, nkv, hd)
        a = _attention(_rope(q, theta), _rope(k, theta), v, 0, q_block)
        x = x + a @ attn["wo"]
        g = rms(x, ln2).reshape(b * s, d)
        m, logits, balance, z = _mixture(
            g, moe, sizes, None if ids is None else ids.reshape(b * s, -1)
        )
        return x + m.reshape(b, s, d), (logits.reshape(b, s, -1), balance, z)

    x, (router_logits, balance, z) = jax.lax.scan(
        layer, x, (params["layers"], choices)
    )
    x = rms(x, _f32(params["final_norm"]))
    routed = {
        "router_logits": router_logits,
        "moe_lb_loss": sizes["moe_aux_coef"] * jnp.sum(balance),
        "moe_z_loss": sizes["moe_z_coef"] * jnp.sum(z),
    }
    return x @ params["lm_head"]["w"].astype(F32), routed


def _mean_ce(logits, targets):
    logz = jax.nn.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, targets[..., None], -1)[..., 0]
    return jnp.mean(logz - tgt)


def loss_and_logits(params, batch, sizes, q_block=1024):
    """Free-running: the reference's own top-k. Mean next-token
    cross-entropy (no router term) and the logits."""
    logits, _ = forward(params, batch["tokens"], sizes, q_block)
    return _mean_ce(logits, batch["targets"]), logits


def loss_and_logits_routed(params, batch, sizes, q_block, choices):
    """Teacher-forced: every token goes to the experts ``choices``
    names. Mean cross-entropy, logits, and the router's side: its logits
    per layer and the router losses."""
    logits, routed = forward(
        params, batch["tokens"], sizes, q_block, choices
    )
    return _mean_ce(logits, batch["targets"]), logits, routed
