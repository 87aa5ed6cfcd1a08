"""Plain reference for Qwen3-Next-80B-A3B-Instruct (``model_type:
qwen3_next``; https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct/
blob/main/config.json): the forward pass and the mean next-token loss in
straightforward ``jax.numpy`` and float32. No kernel, no chunk, no sort,
no ``ragged_dot``, no capacity, no drop, no remat. The caller runs it
under ``jax.default_matmul_precision("highest")``.

It reads the program's parameter tree by name (``layers`` holding one
stack a kind of part: ``gdn``, ``attention``, ``experts``) and the
configuration file's ``sizes``. A layer is TWO parts,
``sizes["layer_pattern"]`` names them (``Ge`` a linear layer, ``*e`` a
full one, ``full_attention_interval`` 4):

    x <- x + mixer(zrms(x; ln));  x <- x + routed(zrms(x; ln))

with ``zrms(x; w) = x / rms(x) * (1 + w)`` the ZERO-CENTRED RMSNorm
(``w`` is what the tree stores; eps ``sizes["norm_eps"]``): the layers'
input norms, the final norm and the attention's q and k norms. The
mixer's output norm is NOT zero-centred.

``G``, a gated-delta-rule mixer (Hk key heads of Dk channels, Hv value
heads of Dv; key head j serves value heads R j .. R j + R - 1, R =
Hv / Hk: repeat-interleave), on u = zrms(x):

    [q | k | v | z] = u W_qkvz;  [b | a] = u W_ba
    [q | k | v]_t <- silu( sum_{j<K} c_j * [q | k | v]_{t-K+1+j} )
              (depthwise, causal, no bias; z, b, a are not convolved)
    beta_t,h = sigmoid(b_t,h)
    g_t,h = -exp(A_log_h) softplus(a_t,h + dt_bias_h)
    q, k <- q / |q|, k / |k| a head (eps 1e-6 under the root)
    q <- q / sqrt(Dk)
    S' = exp(g_t,h) S_{t-1,h}                          (Dk x Dv; S_-1 = 0)
    S_t,h = S' + beta_t,h k_t (v_t,h - S'^T k_t)^T
    o_t,h = S_t,h^T q_t
    y_t,h = o_t,h / rms(o_t,h) * w_norm * silu(z_t,h)  (ONE w_norm of Dv;
              the norm BEFORE the gate)
    out_t = y_t W_out

The recurrence is run AS WRITTEN, token by token (``lax.scan`` over t):
the program's chunked form (a triangular inverse a chunk) is another
algorithm for the same numbers.

``*``, a gated attention of n_head query and n_kv_head key-value heads
of d_head channels, no bias:

    q, k <- zrms_head(q), zrms_head(k)       (one w of d_head each)
    rope (theta ``rope_theta``) on the FIRST ``partial_rotary_factor`` x
        d_head channels of a head, rotate-half inside them; the rest pass
    o_t,h = sum_{s<=t} softmax_s(q_t,h . k_s,g(h) / sqrt(d_head)) v_s,g(h)
    out_t = (o_t * sigmoid(u W_g)) W_o

``e``, the routed block (a layer's second part):

    p = softmax(u W_r)  (float32, over all n_experts)
    e_1..k = the k experts with the largest p (free-running), or the
             ids handed in (teacher-forced)
    w_j = p[e_j] / sum_j p[e_j]      (``norm_topk_prob``: over ALL k
             chosen, held here or not)
    out = sum_{j: e_j held here} w_j swiglu_{e_j}(u)
          + sigmoid(u w_sg) swiglu_shared(u)

The chip holds experts ``[expert_offset, expert_offset +
n_experts_held)`` of the router's ``n_experts``: what the others would
have added is left out, here as in the program, and that partial sum is
what goes on. Then the final zero-centred RMSNorm and the untied head
over the vocabulary held here. Every held expert runs over every token,
one after another, its output scaled by the token's weight for it (zero
where the token did not choose it). Under teacher forcing the ids are
the ONLY thing taken from the program.

Departures from the published model, each so that program and reference
can agree, each listed in the configuration file:

- ``in_proj_qkvz`` and ``in_proj_ba`` are laid out in blocks ([q | k | v
  | z], [b | a]) where the checkpoint interleaves them a key head: a
  permutation of columns;
- the attention's gate is a matrix of its own (``wg``) where the
  checkpoint holds it as the second half of ``q_proj``'s columns a
  head: the same function;
- no module for multi-token prediction (``config.json`` has no key for
  one) and no router loss (it carries no coefficient).

``required_terms`` counts, beside the matrices, the recurrence's own
work as ``nemotron_h_plain`` does Mamba-2's: a token and value head
decays the state (a multiply a cell: half a multiply-add), reads S'^T k,
writes the rank-one change and reads S^T q out (a multiply-add a cell
each): 3.5 x Dk x Dv multiply-adds, entered as that many MULTIPLIED
PARAMETERS (1,835,008 a layer at the published sizes; 0.033 of 1.587
GFLOP a token in the cell's one period). The conv's K taps a channel are not
counted, nor the L2 norms.
"""

import jax
import jax.numpy as jnp

from benchmarks.lib import flops
from benchmarks.references.decoder_plain import F32, _attention

PART_NAMES = {"G": "gdn", "*": "attention", "e": "experts"}


def gdn_multiply_adds(sizes):
    """Multiply-adds the recurrence itself costs a token and layer."""
    return int(
        3.5 * sizes["gdn_value_heads"] * sizes["gdn_key_dim"]
        * sizes["gdn_value_dim"]
    )


def required_terms(sizes, seq):
    """The two terms of ``lib/flops.py``'s convention for this
    architecture on this chip: parts counted kind by kind, a chip that
    holds h of E experts counting k * h / E of them a token, the shared
    expert and its gate whole, the recurrence as the docstring says, the
    vocabulary as sliced."""
    d = sizes["d_model"]
    inner = sizes["gdn_value_heads"] * sizes["gdn_value_dim"]
    keys = sizes["gdn_key_heads"] * sizes["gdn_key_dim"]
    d_attn = sizes["n_head"] * sizes["d_head"]
    met = sizes["expert_top_k"] * sizes["n_experts_held"] / sizes["n_experts"]
    part = {
        "G": (
            d * (2 * keys + 2 * inner + 2 * sizes["gdn_value_heads"])
            + inner * d + gdn_multiply_adds(sizes)
        ),
        # q, the gate and o; k and v
        "*": 3 * d * d_attn + 2 * d * sizes["n_kv_head"] * sizes["d_head"],
        "e": (
            d * sizes["n_experts"] + 3 * d * sizes["d_shared_expert"] + d
            + met * 3 * d * sizes["d_expert"]
        ),
    }
    pattern = sizes["layer_pattern"]
    return {
        "multiplied_params": int(
            sum(part[c] for c in pattern) + d * sizes["vocab_size"]
        ),
        "attention_pair_channels": (
            pattern.count("*") * d_attn * flops.mean_span(seq)
        ),
    }


def _f32(tree):
    return jax.tree.map(lambda w: w.astype(F32), tree)


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)


def _zrms(x, p, sizes):
    """The zero-centred RMSNorm: times 1 + w."""
    return _rms(x, sizes["norm_eps"]) * (1.0 + p["scale"].astype(F32))


def _l2(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


def _delta_rule(u, gdn, sizes):
    """u [B, S, d] -> (the mixer's output [B, S, d], the mean square of
    the read-out before its norm)."""
    b, s, _ = u.shape
    hk, hv = sizes["gdn_key_heads"], sizes["gdn_value_heads"]
    dk, dv = sizes["gdn_key_dim"], sizes["gdn_value_dim"]
    taps = sizes["conv_kernel"]
    keys, inner = hk * dk, hv * dv
    qkv, z = jnp.split(u @ gdn["w_qkvz"], [2 * keys + inner], axis=-1)
    beta, a = jnp.split(u @ gdn["w_ba"], 2, axis=-1)
    padded = jnp.pad(qkv, ((0, 0), (taps - 1, 0), (0, 0)))
    qkv = jax.nn.silu(
        sum(padded[:, j:j + s] * gdn["conv_w"][j] for j in range(taps))
    )
    q, k, v = jnp.split(qkv, [keys, 2 * keys], axis=-1)
    # every value head its key head's q and k: neighbours share
    q = jnp.repeat(_l2(q.reshape(b, s, hk, dk)) * dk ** -0.5, hv // hk, 2)
    k = jnp.repeat(_l2(k.reshape(b, s, hk, dk)), hv // hk, 2)
    v = v.reshape(b, s, hv, dv)
    beta = jax.nn.sigmoid(beta)
    decay = jnp.exp(
        -jnp.exp(gdn["a_log"]) * jax.nn.softplus(a + gdn["dt_bias"])
    )

    def token(state, inp):
        q_t, k_t, v_t, beta_t, decay_t = inp
        state = decay_t[..., None, None] * state
        seen = jnp.einsum("bhde,bhd->bhe", state, k_t)
        state = state + (beta_t[..., None] * k_t)[..., None] * (
            (v_t - seen)[:, :, None, :]
        )
        return state, jnp.einsum("bhde,bhd->bhe", state, q_t)

    _, o = jax.lax.scan(
        token, jnp.zeros((b, hv, dk, dv), F32),
        jax.tree.map(
            lambda t: jnp.moveaxis(t, 1, 0), (q, k, v, beta, decay)
        ),
    )
    o = jnp.moveaxis(o, 0, 1)                              # [B, S, Hv, Dv]
    y = _rms(o, sizes["norm_eps"]) * gdn["norm"]["scale"]
    y = y.reshape(b, s, inner) * jax.nn.silu(z)
    return y @ gdn["w_out"], jnp.mean(o * o)


def _partial_rope(x, sizes):
    """x [B, S, H, D]: the first ``partial_rotary_factor`` x D channels
    of a head turned (lane i pairs with lane i + half of THEM), the rest
    as they are. Positions 0..S-1."""
    s = x.shape[1]
    n = int(x.shape[-1] * sizes["partial_rotary_factor"])
    inv = sizes["rope_theta"] ** (-jnp.arange(0, n, 2, dtype=F32) / n)
    ang = jnp.arange(s, dtype=F32)[None, :, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2, rest = x[..., : n // 2], x[..., n // 2:n], x[..., n:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], -1
    )


def _gated_attention(u, attn, sizes, q_block):
    b, s, _ = u.shape
    nh, nkv, hd = sizes["n_head"], sizes["n_kv_head"], sizes["d_head"]
    q = _zrms((u @ attn["wq"]).reshape(b, s, nh, hd), attn["q_norm"], sizes)
    k = _zrms((u @ attn["wk"]).reshape(b, s, nkv, hd), attn["k_norm"], sizes)
    v = (u @ attn["wv"]).reshape(b, s, nkv, hd)
    o = _attention(
        _partial_rope(q, sizes), _partial_rope(k, sizes), v, 0, q_block
    )
    return (o * jax.nn.sigmoid(u @ attn["wg"])) @ attn["wo"]


def _swiglu(u, w_gate, w_up, w_down):
    return (jax.nn.silu(u @ w_gate.astype(F32)) * (u @ w_up.astype(F32))) \
        @ w_down.astype(F32)


def _routed(u, moe, sizes, ids):
    """u [T, d], ids [T, k] or None -> (this chip's part of the block's
    output [T, d], router logits [T, E])."""
    k = sizes["expert_top_k"]
    first, held = sizes["expert_offset"], sizes["n_experts_held"]
    logits = u @ moe["w_gate"].astype(F32)
    prob = jax.nn.softmax(logits, axis=-1)
    if ids is None:
        ids = jax.lax.top_k(prob, k)[1]
    top = jnp.take_along_axis(prob, ids, axis=-1)
    if sizes["moe_renorm_topk"]:
        top = top / jnp.sum(top, -1, keepdims=True)
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
    own = jax.nn.sigmoid(u @ shared["w_own_gate"].astype(F32))
    return out + own * _swiglu(
        u, shared["w_gate"], shared["w_up"], shared["w_down"]
    ), logits


def _part(x, letter, p, sizes, q_block, ids=None):
    """One part. Returns (x, router logits [B, S, E] or None, the
    read-out's mean square or None)."""
    b, s, d = x.shape
    u = _zrms(x, p["ln"], sizes)
    if letter == "G":
        out, readout = _delta_rule(u, _f32(p["gdn"]), sizes)
        return x + out, None, readout
    if letter == "*":
        out = _gated_attention(u, _f32(p["attn"]), sizes, q_block)
        return x + out, None, None
    # the experts are cast one at a time, inside
    out, logits = _routed(
        u.reshape(b * s, d), p["moe"], sizes,
        None if ids is None else ids.reshape(b * s, -1),
    )
    return x + out.reshape(b, s, d), logits.reshape(b, s, -1), None


def forward(params, tokens, sizes, q_block=1024, choices=None):
    """tokens [B, S] -> (logits [B, S, vocab] float32, router logits
    [routed blocks, B, S, E] before the softmax, which is monotone: the
    top-k is the same; the read-outs' mean square, mean over the linear
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
    x = _zrms(x, params["final_norm"], sizes)
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
    ROUTER_LOSS_TOL, since a uniform scale of o hides from the logits
    behind the norm a head. ISSUE 63's condition for it, at most 1e-3
    over a dozen seeds on the chip, is met (PERF.md section 7)."""
    logits, router_logits, readout = forward(
        params, batch["tokens"], sizes, q_block, choices
    )
    routed = {"router_logits": router_logits, "gdn_readout_ms": readout}
    return _mean_ce(logits, batch["targets"]), logits, routed
