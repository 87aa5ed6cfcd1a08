"""Operations a training step REQUIRES per token: the numerator of
``train_step.mfu``.

Convention (fixed here so that no later change can move it):

    required FLOPs per token
        = 6 x (parameters that are multiplied)
        + 12 x (attention pair-channels)

- "Parameters that are multiplied": every projection and MLP matrix of
  every layer a token passes on this chip, and the output head once. Not
  the embedding gather, not the learned position table, not norm scales
  or biases: a gather and an elementwise scale are not matrix
  multiplications. A tied head counts once, as the head. The 6 is
  forward (2) plus backward (4).
- "Attention pair-channels": the sum over the attention layers of
  heads x (score channels + value channels) / 2 x ``mean_span``. The
  score and the value matmul cost, forward and backward, 2 x 3 = 6
  operations per query-key pair and channel each: 12 where a head has
  as many score channels as value channels, which is what the halved
  sum makes of the two where it has not (latent attention: 192 + 64
  against 256). ``mean_span`` is the mean number of visible keys per
  query under the causal mask and the sliding window: for s queries,
  window w (0 = none), query i (0-based) sees min(i + 1, w or s) keys.
- A layer whose queries attend to a SELECTION of at most k of their
  visible keys (a learned sparse attention: an indexer ranks the keys,
  the attention runs over the k best) counts the selection, not the
  span it was taken from: query i sees min(i + 1, k) keys, the window
  capping it further: ``mean_span(seq, window, topk=k)``. The keys a
  query never multiplies are work the model does not require, however
  the program lays its kernel out.
- A selection made by BLOCKS of b keys (a block-sparse attention: k
  blocks a query, its own among them, which the causal mask cuts)
  counts the keys of the chosen blocks that the query may see: query
  i, in block i // b, sees min(i // b + 1, k) - 1 whole blocks and
  i mod b + 1 keys of its own: ``mean_span(seq, topk=k, block=b)``.
- A scorer that only RANKS keys (such an indexer: a score product and
  no value product) counts its projections among the multiplied
  parameters and heads x (score channels + 0) / 2 x the mean span of
  the keys it SCORES, which is every visible one (``mean_span(seq,
  window)``, no ``topk``): it has to look at a key to pass it over. A
  loss that aligns the scorer with probabilities the layer computes
  anyway counts nothing more. A scorer over POOLED keys (one mean of
  ``window`` keys every ``stride``, scored once its window has ended)
  counts heads x score channels / 2 x the mean number of pooled keys a
  query scores, ``mean_span(seq) / stride`` to within a window; the
  pooling itself, a sum, multiplies nothing.
- Recomputation (remat) does not count: it is work the recipe chose,
  not work the model requires.
- Layers are counted KIND BY KIND, not ``n_layer`` times one: a leading
  dense layer, a routed layer, a layer of another attention each at its
  own widths.
- A routed layer counts what a token meets: the experts it is sent to
  and the router's ``d_model x n_experts`` matrix, at the router's full
  width, not the experts it never visits. Sort, gather and scatter
  multiply nothing. A shared expert, which every token meets, counts
  whole. A chip that holds ``h`` of ``E`` routed experts counts
  ``expert_top_k x h / E`` experts a token: its share under balanced
  routing. PROVISIONAL: no cell holds a part of its experts yet and no
  chip reading stands behind the clause. At seeded weights routing is
  not balanced (``moe.max_expert_load`` reads about 5 on the one routed
  cell there is), so the rows that ``h`` held experts receive can lie
  far from ``h / E`` of them, and ``train_step.mfu`` then reads too
  high or too low by that ratio. The PR that brings the first such
  configuration reads, on the chip and over its seeds, the rows its
  held experts received against ``h / E`` (the program's row counter)
  and states the ratio in PERF.md; where it is not within a few
  percent of 1, the clause is replaced (by a count from that counter)
  in a ``benchmark`` issue before the cell is added.
- An extra prediction module (multi-token prediction) counts what a
  token is multiplied by in it: its projection, its block and the head
  once more.
- A vocabulary sliced over chips counts the slice this chip multiplies.

The convention is this file's. What an architecture IS lives with its
equations: a configuration's reference module
(``benchmarks/references/<module>.py``) may define

    required_terms(sizes, seq) -> {"multiplied_params": int,
                                   "attention_pair_channels": float}

by the clauses above, and ``resolve`` turns the two terms into the
count. A module without it gets ``default_terms``: ``n_layer`` times one
layer of ``n_head`` heads of ``d_model // n_head`` channels, q/k/v/o of
that size (grouped-query k and v at ``n_kv_head``), one MLP of ``d_ff``
(``expert_top_k`` of them and the router where ``n_experts`` > 0), and
the head: every configuration up to PR 32, to the last bit.

This reads LOWER than a count of 6 x every parameter (embedding and
position table included) plus 12 x L x d x span with no causal half
for the same speed.

Sizes come from the configuration file's ``sizes`` group, in the
program's own vocabulary (``n_layer``, ``d_model``, ``n_head``,
``n_kv_head``, ``d_ff``, ``vocab_size``, ``act``, ``attn_window``,
and for a routed model ``n_experts`` and ``expert_top_k``; absent or 0
is a dense MLP), and whatever else the reference module's
``required_terms`` reads.
"""

import importlib
import math

TERMS = ("multiplied_params", "attention_pair_channels")


def mean_span(seq: int, window: int = 0, topk: int = 0,
              block: int = 1) -> float:
    """Mean number of keys a query attends to in a causal sequence of
    ``seq`` tokens under a sliding window of ``window`` keys (0 = no
    window) and a selection of at most ``topk`` of them (0 = none), or,
    with ``block`` > 1, of at most ``topk`` BLOCKS of ``block`` keys,
    the query's own among them."""
    if block > 1:
        if window or seq % block:
            raise ValueError(
                "a selection by blocks takes whole blocks and no window"
            )
        units = seq // block
        k = min(topk, units) if topk else units
        # block u's queries: min(u + 1, k) - 1 whole blocks each, and
        # 1..block keys of their own
        whole = k * (k - 1) // 2 + (units - k) * (k - 1)
        return (whole * block * block + units * block * (block + 1) / 2) / seq
    w = min(window, seq) if window else seq
    if topk:
        w = min(w, topk)
    # queries 0..w-1 see 1..w keys; the remaining seq-w see w each
    return (w * (w + 1) / 2 + (seq - w) * w) / seq


def multiplied_params(sizes: dict) -> int:
    """The default architecture's: every layer the same block."""
    d = sizes["d_model"]
    head_dim = d // sizes["n_head"]
    kv = sizes.get("n_kv_head") or sizes["n_head"]
    attn = 2 * d * sizes["n_head"] * head_dim + 2 * d * kv * head_dim
    mlp = (3 if sizes["act"] == "swiglu" else 2) * d * sizes["d_ff"]
    n_experts = sizes.get("n_experts") or 0
    if n_experts:
        mlp = sizes["expert_top_k"] * mlp + d * n_experts
    head = d * sizes["vocab_size"]
    return sizes["n_layer"] * (attn + mlp) + head


def default_terms(sizes: dict, seq: int) -> dict:
    """The two terms for a stack of ``n_layer`` equal layers whose heads
    have ``d_model // n_head`` score and as many value channels."""
    d_attn = sizes["n_head"] * (sizes["d_model"] // sizes["n_head"])
    span = mean_span(seq, sizes.get("attn_window", 0))
    return {
        "multiplied_params": multiplied_params(sizes),
        "attention_pair_channels": sizes["n_layer"] * d_attn * span,
    }


def flops_of(terms: dict) -> float:
    """The convention: 6 a multiplied parameter, 12 a pair-channel."""
    if set(terms) != set(TERMS):
        raise ValueError(
            f"required_terms gives {sorted(terms)}, the convention "
            f"counts {sorted(TERMS)}"
        )
    for name in TERMS:
        if not (math.isfinite(terms[name]) and terms[name] > 0):
            raise ValueError(f"{name} = {terms[name]!r} counts nothing")
    return (
        6.0 * terms["multiplied_params"]
        + 12.0 * terms["attention_pair_channels"]
    )


def required_flops_per_token(sizes: dict, seq: int) -> float:
    """The default architecture's count: what every configuration whose
    reference module defines no ``required_terms`` reads."""
    return flops_of(default_terms(sizes, seq))


def resolve(config: dict, seq: int) -> float:
    """Required FLOPs per token of a whole configuration file: by its
    reference module's ``required_terms`` where it defines one, else
    the default. The runner calls this once
    (``run["required_flops_per_token"]``)."""
    reference = importlib.import_module(
        "benchmarks.references." + config["reference"]
    )
    terms = getattr(reference, "required_terms", default_terms)
    return flops_of(terms(config["sizes"], seq))
