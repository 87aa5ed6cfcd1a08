"""A small plain reference for a routed decoder, kept with the tests:
the dense family's block (``references/decoder_plain.py``: norms, rope,
grouped-query attention, window) with the MLP replaced by a dropless
top-k mixture of SwiGLU experts, Mixtral's layer (arXiv 2401.04088).
It is what ``tests/test_rehearsal.py`` and the chip rehearsal of the
routed comparison (PERF.md section 6, PR 28) were judged against; no
committed configuration names it. A configuration for a routed model
brings a reference of its own in ``references/`` to this contract.

    h = norm2(x);  l = h W_r  (router logits, float32);  p = softmax(l)
    e_1..k = the k experts with the largest p (free-running), or the
             ids handed in (teacher-forced); w_j = p[e_j], divided by
             their sum unless ``sizes["moe_renorm_topk"]`` is false
    x = x + sum_j w_j (silu(h Wg[e_j]) * (h Wu[e_j])) Wd[e_j]

Every expert runs over every token, one expert after another, its
output masked by the token's weight for it: no sort, no ``ragged_dot``,
no capacity, no drop. The router losses are the program's
(``parallel/moe.py``), per layer and summed over layers:
``moe_lb_loss`` = aux_coef x E x sum_e f_e pbar_e with f_e expert e's
share of the (token, choice) pairs and pbar_e its mean probability;
``moe_z_loss`` = z_coef x mean(logsumexp(l)^2).

Under teacher forcing the ids are the ONLY thing taken from the
program: hidden states, router logits, probabilities, combine weights,
experts and losses are this file's own.
"""

import jax
import jax.numpy as jnp

from benchmarks.references.decoder_plain import F32, _attention, _norm, _rope


def _experts(h, moe, sizes, ids):
    """h [T, d], ids [T, k] or None -> (mixture output [T, d], router
    logits [T, E], balance term, z term)."""
    n_exp, k = sizes["n_experts"], sizes["expert_top_k"]
    logits = h @ moe["w_gate"].astype(F32)
    probs = jax.nn.softmax(logits, axis=-1)
    if ids is None:
        ids = jax.lax.top_k(probs, k)[1]
    top_w = jnp.take_along_axis(probs, ids, axis=-1)
    if sizes.get("moe_renorm_topk", True) and k > 1:
        top_w = top_w / jnp.sum(top_w, -1, keepdims=True)
    chosen = ids[:, :, None] == jnp.arange(n_exp)[None, None, :]  # [T, k, E]
    weight = jnp.sum(jnp.where(chosen, top_w[:, :, None], 0.0), axis=1)

    def expert(total, args):
        w_gate_proj, w_up, w_down, w_tok = args
        y = jax.nn.silu(h @ w_gate_proj.astype(F32)) * (h @ w_up.astype(F32))
        return total + (y @ w_down.astype(F32)) * w_tok[:, None], None

    out, _ = jax.lax.scan(
        expert, jnp.zeros_like(h),
        (moe["w_gate_proj"], moe["w_up"], moe["w_down"], weight.T),
    )
    share = jnp.mean(chosen.astype(F32), axis=(0, 1))  # sums to 1
    balance = n_exp * jnp.sum(share * jnp.mean(probs, axis=0))
    z = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
    return out, logits, balance, z


def forward(params, tokens, sizes, q_block=1024, choices=None):
    """tokens [B, S] -> (logits [B, S, vocab] float32, routed) with
    ``routed`` the router logits [L, B, S, E] and the two router
    losses. ``choices`` int32 [L, B, S, k] forces the routing."""
    b, s = tokens.shape
    nh, d = sizes["n_head"], sizes["d_model"]
    nkv = sizes.get("n_kv_head") or nh
    hd = d // nh
    kind, eps = sizes["norm"], sizes["norm_eps"]
    x = jnp.take(params["embed"]["tokens"], tokens, axis=0).astype(F32)
    if sizes["pos"] == "learned":
        x = x + params["pos_embed"]["table"][:s].astype(F32)[None]

    def layer(x, inp):
        p, ids = inp
        moe = p["moe"]  # cast expert by expert: a layer's experts are large
        p = jax.tree.map(
            lambda w: w.astype(F32), {n: p[n] for n in ("ln1", "ln2", "attn")}
        )
        h = _norm(x, p["ln1"], kind, eps)
        q = (h @ p["attn"]["wq"]).reshape(b, s, nh, hd)
        k = (h @ p["attn"]["wk"]).reshape(b, s, nkv, hd)
        v = (h @ p["attn"]["wv"]).reshape(b, s, nkv, hd)
        if sizes["pos"] == "rope":
            q, k = _rope(q, sizes["rope_theta"]), _rope(k, sizes["rope_theta"])
        a = _attention(q, k, v, sizes.get("attn_window", 0), q_block)
        x = x + a @ p["attn"]["wo"]
        h = _norm(x, p["ln2"], kind, eps).reshape(b * s, d)
        m, logits, balance, z = _experts(
            h, moe, sizes, None if ids is None else ids.reshape(b * s, -1)
        )
        return x + m.reshape(b, s, d), (logits.reshape(b, s, -1), balance, z)

    x, (router_logits, balance, z) = jax.lax.scan(
        layer, x, (params["layers"], choices)
    )
    x = _norm(x, jax.tree.map(lambda w: w.astype(F32), params["final_norm"]),
              kind, eps)
    if sizes["tie_embeddings"]:
        head = params["embed"]["tokens"].astype(F32).T
    else:
        head = params["lm_head"]["w"].astype(F32)
    routed = {
        "router_logits": router_logits,
        "moe_lb_loss": sizes["moe_aux_coef"] * jnp.sum(balance),
        "moe_z_loss": sizes["moe_z_coef"] * jnp.sum(z),
    }
    return x @ head, routed


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
