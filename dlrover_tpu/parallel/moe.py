"""Mixture-of-Experts with expert parallelism over the ``ep`` mesh axis.

Reference: atorch/atorch/modules/moe/moe_layer.py (MOELayer with explicit
``_AllToAll`` autograd ops and expert process groups) and grouped_gemm_moe.py.
TPU-native design: token-choice top-k gating lowered to dense one-hot
dispatch/combine einsums; sharding the expert axis over ``ep`` makes XLA
emit the all-to-alls on ICI — no hand-written collectives, and the expert
FFN is a single batched matmul on the MXU (the grouped-GEMM equivalent).
"""

import dataclasses
import functools
from typing import Dict

import jax
import jax.numpy as jnp

from dlrover_tpu.observability.tracing import set_counter
from dlrover_tpu.ops import pallas_rows
from dlrover_tpu.parallel import sharding as shd


def init_moe_params(rng, cfg, lead=None) -> Dict:
    """Stacked per-layer MoE params: layers on axis 0 (``lead``, default
    the trunk's routed layers; ``()`` for one block), experts on the next.
    The router is ``n_experts`` wide whatever number of experts is held
    here (``cfg.experts_here``); a shared expert is one MLP of width
    ``cfg.shared_expert_width`` under ``"shared"``. ``act: relu2`` makes
    every expert two matrices (no ``w_gate_proj`` / ``shared.w_gate``);
    ``moe_latent_size`` makes the routed experts that wide at both ends
    and adds the two projections under ``"latent"``."""
    d, f, e = cfg.d_model, cfg.expert_width, cfg.experts_here
    di = cfg.expert_in
    gated = cfg.act != "relu2"
    lead = (cfg.n_routed_layer,) if lead is None else tuple(lead)
    pdt = jnp.dtype(cfg.param_dtype)
    k = jax.random.split(rng, 4)
    s_in = 1.0 / jnp.sqrt(d)
    s_exp = 1.0 / jnp.sqrt(di)

    def draw(key, shape, scale):
        return (jax.random.normal(key, lead + shape) * scale).astype(pdt)

    params = {
        "w_gate": draw(k[0], (d, cfg.n_experts), s_in),
        "w_up": draw(k[1], (e, di, f), s_exp),
        "w_down": draw(k[3], (e, f, di), 1.0 / jnp.sqrt(f)),
    }
    if gated:
        params["w_gate_proj"] = draw(k[2], (e, di, f), s_exp)
    if cfg.moe_latent_size:
        kl = jax.random.split(jax.random.fold_in(rng, 2), 2)
        params["latent"] = {
            "w_down": draw(kl[0], (d, di), s_in),
            "w_up": draw(kl[1], (di, d), s_exp),
        }
    if cfg.n_shared_experts:
        fs = cfg.shared_expert_width
        ks = jax.random.split(jax.random.fold_in(rng, 1), 3)
        params["shared"] = {
            "w_up": draw(ks[1], (d, fs), s_in),
            "w_down": draw(ks[2], (fs, d), 1.0 / jnp.sqrt(fs)),
        }
        if gated:
            params["shared"]["w_gate"] = draw(ks[0], (d, fs), s_in)
        if cfg.shared_expert_gate:
            params["shared"]["w_own_gate"] = draw(
                jax.random.fold_in(rng, 3), (d, 1), s_in
            )
    return params


def moe_logical_axes(cfg, lead=("layers",)) -> Dict:
    lead = tuple(lead)
    gated = cfg.act != "relu2"
    # experts in a latent read rows that are not the embed axis
    rows = None if cfg.moe_latent_size else "embed"
    ax = {
        "w_gate": lead + ("embed", None),
        "w_up": lead + ("expert", rows, "mlp"),
        "w_down": lead + ("expert", "mlp", rows),
    }
    if gated:
        ax["w_gate_proj"] = lead + ("expert", rows, "mlp")
    if cfg.moe_latent_size:
        ax["latent"] = {
            "w_down": lead + ("embed", None),
            "w_up": lead + (None, "embed"),
        }
    if cfg.n_shared_experts:
        ax["shared"] = {
            "w_up": lead + ("embed", "mlp"),
            "w_down": lead + ("mlp", "embed"),
        }
        if gated:
            ax["shared"]["w_gate"] = lead + ("embed", "mlp")
        if cfg.shared_expert_gate:
            ax["shared"]["w_own_gate"] = lead + ("embed", None)
    return ax


def top_k_gating(
    gate_logits: jax.Array,
    k: int,
    capacity: int,
    renormalize: bool = True,
):
    """Token-choice top-k routing with per-sequence capacity.

    gate_logits: [B, S, E] → (dispatch [B,S,E,C] bool, combine [B,S,E,C],
    probs [B,S,E]). Tokens overflowing an expert's capacity are dropped
    (standard GShard behavior; the residual connection carries them
    through).

    ``renormalize``: rescale combine weights to sum to 1 over kept
    choices (Mixtral-style). MUST be False for k=1: renormalizing a
    single choice yields the constant 1.0, which has zero derivative
    w.r.t. the router logits — the router would never train.
    """
    return _capacity_gating(gate_logits, k, capacity, renormalize)[:3]


def _capacity_gating(gate_logits, k: int, capacity: int, renormalize: bool):
    """``top_k_gating`` plus the top-k expert ids [B,S,k] as chosen,
    before any drop."""
    b, s, e = gate_logits.shape
    probs = jax.nn.softmax(gate_logits.astype(jnp.float32), axis=-1)
    # the choice is _topk_weights' (top-k of the probabilities); the
    # weights are not: renormalization here runs over KEPT choices,
    # after drops
    gate_vals, gate_idx = jax.lax.top_k(probs, k)  # [B,S,k]
    # one-hot expert assignment per choice: [B, S, k, E]
    assign = jax.nn.one_hot(gate_idx, e, dtype=jnp.float32)
    # position of each (token, choice) in its expert's buffer, counted over
    # the flattened (S, k) order.
    flat = assign.reshape(b, s * k, e)
    pos = jnp.cumsum(flat, axis=1) - flat  # [B, S*k, E]
    pos = pos.reshape(b, s, k, e)
    in_cap = pos < capacity
    assign = assign * in_cap
    pos = jnp.einsum("bske,bske->bsk", pos, assign)  # chosen slot per choice
    slot = jax.nn.one_hot(pos.astype(jnp.int32), capacity, dtype=jnp.float32)
    kept = assign.sum(-1)  # [B,S,k] 1 if kept
    if renormalize:
        # renormalise combine weights over kept choices
        denom = jnp.maximum((gate_vals * kept).sum(-1, keepdims=True), 1e-9)
        weights = gate_vals * kept / denom
    else:
        # raw router probability (Switch: y = p_i(x)·E_i(x)) keeps the
        # router differentiable through the combine path
        weights = gate_vals * kept
    dispatch = jnp.einsum("bske,bskc->bsec", assign, slot)
    combine = jnp.einsum("bsk,bske,bskc->bsec", weights, assign, slot)
    return dispatch, combine, probs, gate_idx


def switch_gating(
    gate_logits: jax.Array,
    capacity: int,
    jitter_eps: float = 0.0,
    rng=None,
):
    """Switch-Transformer top-1 routing (reference: moe/switch_gating.py).

    Multiplicative jitter noise on the router logits during training
    (``rng`` given) decorrelates expert assignment, per the Switch paper.
    """
    gate_logits = _jitter(gate_logits, jitter_eps, rng)
    return top_k_gating(gate_logits, 1, capacity, renormalize=False)


def load_balancing_loss(probs: jax.Array, dispatch: jax.Array) -> jax.Array:
    """GShard aux loss: E · Σ_e f_e · p_e (probs [B,S,E], dispatch [B,S,E,C]).

    Reduced in float32: a bf16 dispatch tensor summed over thousands of
    tokens would round the per-expert counts (bf16 only represents
    integers exactly up to 256) and bias the loss.
    """
    e = probs.shape[-1]
    dispatch = dispatch.astype(jnp.float32)
    frac_tokens = dispatch.sum(-1).mean(axis=(0, 1))  # [E]
    frac_probs = probs.astype(jnp.float32).mean(axis=(0, 1))  # [E]
    return e * jnp.sum(frac_tokens * frac_probs)


def router_z_loss(gate_logits: jax.Array) -> jax.Array:
    """ST-MoE router z-loss: mean logsumexp² keeps router logits small."""
    logz = jax.nn.logsumexp(gate_logits.astype(jnp.float32), axis=-1)
    return jnp.mean(logz**2)


def _jitter(gate_logits, jitter_eps, rng):
    """Switch-paper multiplicative router noise (train only)."""
    if jitter_eps > 0.0 and rng is not None:
        noise = jax.random.uniform(
            rng,
            gate_logits.shape,
            minval=1.0 - jitter_eps,
            maxval=1.0 + jitter_eps,
            dtype=gate_logits.dtype,
        )
        gate_logits = gate_logits * noise
    return gate_logits


def _topk_weights(probs, k: int, renormalize: bool):
    """Top-k choice + combine weights of the dropless (ragged) path:
    the k largest probabilities, divided by their sum when
    ``renormalize``. The capacity path (``_capacity_gating``) makes the
    same choice but renormalizes over the choices it KEPT. Both take
    the flag from ``_renormalize(cfg)``, so a model's rule holds in
    every lowering.

    ``renormalize`` is ignored for k=1: renormalizing a single choice
    yields the constant 1.0, which has zero derivative w.r.t. the router
    logits — the router would never train. Raw router probability
    (Switch: y = p_i(x)·E_i(x)) keeps it differentiable."""
    gate_vals, gate_idx = jax.lax.top_k(probs, k)
    if renormalize and k > 1:
        weights = gate_vals / jnp.maximum(
            gate_vals.sum(-1, keepdims=True), 1e-9
        )
    else:
        weights = gate_vals
    return weights, gate_idx


def _renormalize(cfg) -> bool:
    """Whether the model's combine weights are divided by their sum:
    ``cfg.moe_renorm_topk`` (Mixtral yes, OLMoE no); Switch never."""
    return cfg.moe_renorm_topk and cfg.moe_gating != "switch"


def _router_logits(x, moe, cfg, rng):
    """Router logits [B,S,E] in float32 straight from the matmul (the
    operands stay in the compute dtype): a bf16 result would round the
    logits before the softmax and move near-tied top-k choices. Switch
    jitter applied."""
    gate_logits = jnp.matmul(
        x, moe["w_gate"].astype(x.dtype),
        preferred_element_type=jnp.float32,
    )
    if cfg.moe_gating == "switch":
        gate_logits = _jitter(gate_logits, cfg.moe_jitter, rng)
    return gate_logits


def _route(x, moe, cfg, rng):
    """Shared router entry for the ragged path: logits (+switch jitter)
    → scores (softmax over the experts, or each logit's sigmoid:
    ``cfg.moe_score``), combine weights, expert choices. The top-k and
    the renormalisation run over ALL ``n_experts`` scores, whichever
    experts are held here; ``routed_scaling_factor`` multiplies the
    weights last."""
    with jax.named_scope("moe.route"):
        gate_logits = _router_logits(x, moe, cfg, rng)
        if cfg.moe_score == "sigmoid":
            probs = jax.nn.sigmoid(gate_logits)
        else:
            probs = jax.nn.softmax(gate_logits, axis=-1)
        weights, gate_idx = _topk_weights(
            probs, cfg.routed_top_k, _renormalize(cfg)
        )
        if cfg.routed_scaling_factor != 1.0:
            weights = weights * cfg.routed_scaling_factor
    return gate_logits, probs, weights, gate_idx


def _gate(x, moe, cfg, rng):
    """Router entry of the capacity paths → (dispatch, combine, probs,
    gate_logits, gate_idx [B,S,k] before drops)."""
    k = cfg.routed_top_k
    capacity = max(
        1, int(cfg.capacity_factor * x.shape[1] * k / cfg.n_experts)
    )
    gate_logits = _router_logits(x, moe, cfg, rng)
    dispatch, combine, probs, gate_idx = _capacity_gating(
        gate_logits, k, capacity, _renormalize(cfg)
    )
    return (
        dispatch.astype(x.dtype),
        combine.astype(x.dtype),
        probs,
        gate_logits,
        gate_idx,
    )


def _expert_ffn(expert_in, moe, dtype, fp8=None):
    """[E_local, T, C, D] → [E_local, T, C, D], batched over experts (the
    grouped-GEMM equivalent: one MXU matmul per projection).

    ``fp8="current"``: the three expert GEMMs run as fp8
    current-scaling batched dots (per-expert weight scales,
    ops/fp8.py:fp8_batched_dot_current) — stateless, so it composes
    with every mesh incl. pipeline."""
    if fp8 == "current":
        from dlrover_tpu.ops.fp8 import fp8_batched_dot_current

        e, b, c, d = expert_in.shape
        x3 = expert_in.reshape(e, b * c, d)
        up = fp8_batched_dot_current(x3, moe["w_up"].astype(dtype))
        gate_p = fp8_batched_dot_current(
            x3, moe["w_gate_proj"].astype(dtype)
        )
        h = jax.nn.silu(gate_p) * up
        out = fp8_batched_dot_current(h, moe["w_down"].astype(dtype))
        return out.reshape(e, b, c, d)
    up = jnp.einsum("ebcd,edf->ebcf", expert_in, moe["w_up"].astype(dtype))
    gate_p = jnp.einsum(
        "ebcd,edf->ebcf", expert_in, moe["w_gate_proj"].astype(dtype)
    )
    h = jax.nn.silu(gate_p) * up
    return jnp.einsum("ebcf,efd->ebcd", h, moe["w_down"].astype(dtype))


def moe_block(
    x: jax.Array,
    moe: Dict,
    cfg,
    mesh=None,
    rng=None,
    return_aux: bool = False,
    fp8=None,
):
    """x: [B,S,D] → [B,S,D]. Expert FFN sharded over the ``ep`` axis.

    ``return_aux`` adds the router's side: ``moe_lb_loss`` and
    ``moe_z_loss`` (before their coefficients), ``moe_choices`` (the
    top-k expert ids of every token, int32 [B,S,k]; before drops on the
    capacity paths) and, on the ragged paths, ``moe_max_load`` (rows of
    the fullest expert over the mean rows an expert gets).

    Three dispatch lowerings:
    - dense einsum (default): capacity-based one-hot dispatch/combine
      einsums + sharding constraints; XLA inserts the expert
      all-to-alls on ICI.
    - explicit all-to-all (``cfg.moe_alltoall``): shard_map over ``ep``
      with ``lax.all_to_all``, the direct analog of the reference's
      ``_AllToAll`` autograd op (moe_layer.py:22) — tokens are sharded
      over ``ep`` too, so each rank routes B/ep of the batch.
    - ragged / dropless (``cfg.moe_impl == "ragged"``): tokens sorted by
      expert + ``lax.ragged_dot`` grouped-GEMM — FLOPs scale with the
      tokens actually routed, no capacity truncation under imbalance
      (reference capability: grouped_gemm_moe.py:46, built there on a
      CUDA grouped-GEMM kernel; ragged_dot is the TPU-native primitive).
    """
    # only the stateless "current" mode reaches the experts (delayed
    # states cover the attention projections; see decoder.init_fp8_states)
    fp8 = "current" if fp8 is not None else None
    if cfg.moe_impl == "ragged":
        # dropless ragged stays bf16 under fp8: lax.ragged_dot has no
        # scaled-fp8 lowering — quantizing would be fake-quant cost with
        # no MXU win (documented limitation, VERDICT r4 ask #4)
        out, aux = _moe_block_ragged(x, moe, cfg, mesh, rng)
    elif (
        cfg.moe_alltoall
        and mesh is not None
        and mesh.shape.get("ep", 1) > 1
    ):
        out, aux = _moe_block_alltoall(x, moe, cfg, mesh, rng, fp8=fp8)
    else:
        out, aux = _moe_block_dense(x, moe, cfg, mesh, rng, fp8)
    if cfg.n_shared_experts:
        out = out + _shared_expert(x, moe["shared"], mesh)
    return (out, aux) if return_aux else out


def _shared_expert(x, shared, mesh):
    """The MLP every token meets beside its routed experts: a SwiGLU,
    or relu(.)² between two matrices where it has no gate; times
    ``sigmoid(x w)`` of its OWN gate ``w_own_gate`` [d, 1] where the
    model has one (``cfg.shared_expert_gate``), the sigmoid and the
    product float32."""
    with jax.named_scope("moe.shared"):
        h = x @ shared["w_up"].astype(x.dtype)
        if "w_gate" in shared:
            h = jax.nn.silu(x @ shared["w_gate"].astype(x.dtype)) * h
        else:
            h = jnp.square(jax.nn.relu(h))
        if mesh is not None:
            h = shd.constrain(h, mesh, "batch", "seq", "mlp")
        out = h @ shared["w_down"].astype(x.dtype)
        if "w_own_gate" in shared:
            own = jnp.matmul(
                x, shared["w_own_gate"].astype(x.dtype),
                preferred_element_type=jnp.float32,
            )
            out = (out * jax.nn.sigmoid(own)).astype(x.dtype)
        return out


def _moe_block_dense(x, moe, cfg, mesh, rng, fp8):
    dispatch, combine, probs, gate_logits, gate_idx = _gate(x, moe, cfg, rng)
    aux = {
        "moe_lb_loss": load_balancing_loss(probs, dispatch),
        "moe_z_loss": router_z_loss(gate_logits),
        "moe_choices": gate_idx,
    }
    # [E, B, C, D]: this einsum is the all-to-all when x is dp-sharded and
    # expert tensors are ep-sharded.
    expert_in = jnp.einsum("bsec,bsd->ebcd", dispatch, x)
    if mesh is not None:
        expert_in = shd.constrain(expert_in, mesh, "expert", "batch", None, None)
    expert_out = _expert_ffn(expert_in, moe, x.dtype, fp8=fp8)
    if mesh is not None:
        expert_out = shd.constrain(
            expert_out, mesh, "expert", "batch", None, None
        )
    return jnp.einsum("ebcd,bsec->bsd", expert_out, combine), aux


def _moe_block_alltoall(x, moe, cfg, mesh, rng, fp8=None):
    from jax.sharding import PartitionSpec as P

    ep = mesh.shape["ep"]
    e = cfg.n_experts
    if e % ep:
        raise ValueError(f"n_experts {e} not divisible by ep {ep}")
    batch_axes = ("dp", "fsdp", "ep")

    def body(xl, w_gate, w_up, w_gp, w_down):
        # xl: [B/(dp·fsdp·ep), S, D] — this rank's token slice.
        local = {
            "w_gate": w_gate,
            "w_up": w_up,
            "w_gate_proj": w_gp,
            "w_down": w_down,
        }
        dispatch, combine, probs, gate_logits, gate_idx = _gate(
            xl, local, cfg, rng
        )
        expert_in = jnp.einsum("bsec,bsd->ebcd", dispatch, xl)  # [E,b,C,D]
        # exchange: every rank sends each expert-owner its slice of tokens
        expert_in = jax.lax.all_to_all(
            expert_in, "ep", split_axis=0, concat_axis=1, tiled=True
        )  # [E/ep, b·ep, C, D]
        expert_out = _expert_ffn(expert_in, local, xl.dtype, fp8=fp8)
        expert_out = jax.lax.all_to_all(
            expert_out, "ep", split_axis=1, concat_axis=0, tiled=True
        )  # [E, b, C, D]
        out = jnp.einsum("ebcd,bsec->bsd", expert_out, combine)
        # the lb loss must use GLOBAL expert statistics: pmean the per-rank
        # [E] fractions first, THEN take the product — mean-of-products
        # over ranks would be a systematically different (upward-biased)
        # loss than the dense lowering computes over the full batch
        e_count = probs.shape[-1]
        frac_tokens = jax.lax.pmean(
            dispatch.astype(jnp.float32).sum(-1).mean(axis=(0, 1)),
            axis_name=batch_axes,
        )
        frac_probs = jax.lax.pmean(
            probs.astype(jnp.float32).mean(axis=(0, 1)),
            axis_name=batch_axes,
        )
        aux = {
            "moe_lb_loss": (
                e_count * jnp.sum(frac_tokens * frac_probs)
            ).astype(jnp.float32),
            # z-loss is a plain mean over tokens: mean of equal-sized
            # per-rank means is the global mean
            "moe_z_loss": jax.lax.pmean(
                router_z_loss(gate_logits), axis_name=batch_axes
            ),
        }
        return out, aux, gate_idx

    out, aux, choices = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(
            P(batch_axes, None, None),
            P(None, None),       # w_gate replicated
            P("ep", None, None),  # expert-sharded FFN weights
            P("ep", None, None),
            P("ep", None, None),
        ),
        out_specs=(P(batch_axes, None, None), P(), P(batch_axes, None, None)),
        check_vma=False,
    )(
        x,
        moe["w_gate"].astype(x.dtype),
        moe["w_up"].astype(x.dtype),
        moe["w_gate_proj"].astype(x.dtype),
        moe["w_down"].astype(x.dtype),
    )
    aux["moe_choices"] = choices
    return out, aux


# ---------------------------------------------------------------------------
# Dropless (ragged grouped-GEMM) lowering
# ---------------------------------------------------------------------------


# Token order and expert order (docs/performance.md): ``order`` is a
# permutation of the t·k (token, choice) pairs and ``inv`` its inverse,
# so rows move between the two orders by GATHER in both directions of
# the derivative, and rows meet only in a dense sum over the k axis of a
# [t, k, d] view. jax's own transpose of a gather is a scatter-add, which
# the TPU serialises when indices repeat (every token row is hit k
# times): a fourteenth of the memory's rate at OLMoE's 65,536 rows. Hence
# the two hand-written derivatives below.


def _rows(x, idx):
    """``x[idx]`` along axis 0 for indices that are in range by
    construction (a permutation, or one folded by ``// k``): without the
    promise every gather is followed by a select over its whole output
    that fills out-of-range rows."""
    return x.at[idx].get(mode="promise_in_bounds")


# Where the device holds a part of the experts (docs/performance.md, "the
# held rows"): the pairs whose expert is here are the PREFIX of the
# expert order — ``_sort_by_expert`` sends the others to the tail —,
# ``Held.rows`` of them, a number the device knows and the trace does
# not. The arrays keep their static rows; the sums over a token's held
# rows (``pallas_rows.rows_sum``), the combine's derivative
# (``_combine_bwd_held``) and the experts' interior between the grouped
# matmuls (``_interior_held``) walk the prefix alone, by that count: no
# static bound, nothing dropped. ``held is None`` — every expert here —
# is the other structure, with nothing to skip, and keeps its own
# program.


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=["mask", "rows"], meta_fields=["one_device"],
)
@dataclasses.dataclass(frozen=True)
class Held:
    """The pairs whose expert is here: ``mask`` [t·k] bool in token
    order, ``rows`` their count (int32 scalar, = ``group_sizes.sum()``);
    ``one_device`` (static): the rows are one device's own, no mesh
    around the call, so the row kernel may run."""

    mask: jax.Array
    rows: jax.Array
    one_device: bool = False

    def tiles(self, t, rows, dtype):
        """``pallas_rows.tile`` for sums of ``rows`` [n, d] into ``t``
        tokens, None where the XLA body runs."""
        if not self.one_device:
            return None
        return pallas_rows.tile(t, *rows.shape, dtype)

    def interior_tiles(self, up, gated):
        """``pallas_rows.act_tile`` for the experts' interior over
        ``up`` [n, d_expert], None where the XLA body runs."""
        if not self.one_device:
            return None
        return pallas_rows.act_tile(*up.shape, up.dtype, gated)


# rows a turn of the loops over the held prefix (chip sweep in
# ``_combine_bwd_held``)
HELD_CHUNK = 1024


def _prefix_turns(held_rows, chunk):
    return (held_rows + chunk - 1) // chunk


def _chunk_start(c, chunk, n):
    """Where turn ``c`` of a loop over chunks of n rows begins: the last
    chunk of an n that ``chunk`` does not divide overlaps the one
    before (what a turn writes depends on the row alone)."""
    return jnp.minimum(c * chunk, n - chunk)


def _held_weights(weights, order, held_rows):
    """``weights`` [t, k] in expert order, [n] float32, over the held
    prefix (whole chunks of it) and 0 behind: a gather of scalars costs
    the chip 8 ns each, so only the prefix's are moved."""
    n = order.shape[0]
    chunk = min(4 * HELD_CHUNK, n)
    flat = weights.reshape(-1).astype(jnp.float32)

    def turn(c, out):
        at = _chunk_start(c, chunk, n)
        pairs = jax.lax.dynamic_slice_in_dim(order, at, chunk)
        return jax.lax.dynamic_update_slice_in_dim(
            out, _rows(flat, pairs), at, 0
        )

    return jax.lax.fori_loop(
        0, _prefix_turns(held_rows, chunk), turn, jnp.zeros((n,), flat.dtype)
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _dispatch(k, xt, token_of, inv, held=None):
    """Token rows [t, d] → expert order [n, d] (``token_of`` =
    ``order // k``). ``held`` (``Held``, None = all): the pairs whose
    expert is here; the others' rows bring nothing back. The forward
    gathers every row either way: the compiler's gather out of the
    32 MB of tokens runs at the memory's write rate (0.45 ms for 65,536
    rows, my chip runs, PR 59), which a loop over the held rows with its
    zero fill does not beat (0.73 ms at 8,194 held)."""
    return _rows(xt, token_of)


def _dispatch_fwd(k, xt, token_of, inv, held=None):
    return _dispatch(k, xt, token_of, inv, held), (token_of, inv, held)


def _dispatch_bwd(k, res, g):
    # the k rows of a token, back in token order, summed in float32
    token_of, inv, held = res
    t = inv.shape[0] // k
    with jax.named_scope("moe.sort"):
        tiles = None if held is None else held.tiles(t, g, g.dtype)
        if tiles is not None:
            # the held rows alone, from the prefix they fill: what lies
            # in rows no expert wrote is never read
            d_xt = pallas_rows.rows_sum(
                g, token_of, None, held.rows, t, g.dtype, tiles
            )
            return d_xt, None, None, None
        d_xt = _rows(g, inv).reshape(-1, k, g.shape[-1])
        if held is not None:
            # a select, not a product: what lies in rows no expert
            # wrote is unspecified
            d_xt = jnp.where(held.mask.reshape(-1, k, 1), d_xt, 0)
        d_xt = d_xt.sum(axis=1, dtype=jnp.float32).astype(g.dtype)
    return d_xt, None, None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


def _held_row_bound(t, k, e, some_elsewhere):
    """Rows of the expert-sorted arrays that can hold a pair whose
    expert is here, where that is fewer than all ``t · k``: a token's k
    choices are distinct, so at most ``min(k, e)`` of them name one of
    the ``e`` experts held. None where every row can (GLM, Keye: k ≤ e)."""
    return t * e if some_elsewhere and e < k else None


def _sort_by_expert(xt, gate_idx, e, some_elsewhere=False, one_device=False):
    """Stable-sort prologue shared by both ragged lowerings: (token,
    choice) pairs ordered by expert. STABILITY is load-bearing — the
    a2a pack/unpack indexing assumes per-expert token order survives.

    ``some_elsewhere``: the ``e`` experts here are a part of the
    router's and ``gate_idx`` is local to them: an id outside [0, e)
    names an expert on another device. Its pairs sort to the tail,
    behind the last group, where ``ragged_dot`` computes nothing, and
    bring no gradient back.

    Where a token chooses more experts than are here (k > e), the held
    pairs fill at most the first ``t · e`` sorted rows
    (``_held_row_bound``): ``order`` and ``sorted_in`` are CUT to them,
    exactly and without a drop, and ``inv`` is clamped into them (the
    pairs it then misplaces are all elsewhere, which ``held`` masks).

    ``one_device``: the rows are one device's own (no mesh around the
    call), so the row kernel may run (``Held``).

    Returns (flat_idx [t·k] (``e`` = not held here), order [n], inv
    [t·k] with ``inv[order] = arange`` on the held pairs, sorted_in
    [n, D], counts [e]); n = t·k or the bound."""
    t, k = gate_idx.shape
    with jax.named_scope("moe.sort"):
        flat_idx = gate_idx.reshape(t * k)
        held = None
        if some_elsewhere:
            held = (flat_idx >= 0) & (flat_idx < e)
            flat_idx = jnp.where(held, flat_idx, e)
        order = jnp.argsort(flat_idx)
        inv = jnp.argsort(order)
        bound = _held_row_bound(t, k, e, some_elsewhere)
        if bound is not None:
            order = order[:bound]
            inv = jnp.minimum(inv, bound - 1)
        if held is not None:
            held = Held(held, jnp.sum(held, dtype=jnp.int32), one_device)
        sorted_in = _dispatch(k, xt, order // k, inv, held)
        counts = jnp.sum(
            flat_idx[:, None] == jnp.arange(e, dtype=flat_idx.dtype),
            axis=0, dtype=jnp.int32,
        )
    return flat_idx, order, inv, sorted_in, counts


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _interior_held(up, gate, held_rows, tiles):
    """The experts' activation between the grouped matmuls over the held
    prefix of the expert order alone (``pallas_rows.experts_act``, and
    ``experts_act_bwd`` coming back): ``ragged_dot`` wrote ``up`` and
    ``gate`` inside its groups, the first ``held_rows`` rows, and reads
    ``h`` and the cotangents there and nowhere else, so the rows behind
    them are neither read nor written."""
    return pallas_rows.experts_act(up, gate, held_rows, tiles)


def _interior_held_fwd(up, gate, held_rows, tiles):
    return _interior_held(up, gate, held_rows, tiles), (up, gate, held_rows)


def _interior_held_bwd(tiles, res, d_h):
    up, gate, held_rows = res
    with jax.named_scope("moe.experts"):
        d_up, d_gate = pallas_rows.experts_act_bwd(
            up, gate, d_h, held_rows, tiles
        )
    return d_up, d_gate, None


_interior_held.defvjp(_interior_held_fwd, _interior_held_bwd)


def _ragged_experts(rows, w_up, w_gate_proj, w_down, group_sizes, held=None):
    """The SwiGLU experts over expert-sorted ``rows`` [N, D] as three
    ragged matmuls (``lax.ragged_dot``: rhs [E, ·, ·], group_sizes = the
    rows each expert actually got — the MXU only sees routed tokens).
    ``w_gate_proj`` None: experts without a gate, relu(.)² between two.
    ``held`` (``Held``, None = every expert here): the activation
    between the matmuls goes by the held prefix where the kernel runs
    (``Held.interior_tiles``; counter ``moe.experts_by_prefix``)."""
    with jax.named_scope("moe.experts"):
        up = jax.lax.ragged_dot(rows, w_up, group_sizes)
        gate = None
        if w_gate_proj is not None:
            gate = jax.lax.ragged_dot(rows, w_gate_proj, group_sizes)
        tiles = None
        if held is not None:
            tiles = held.interior_tiles(up, gate is not None)
        set_counter("moe.experts_by_prefix", int(tiles is not None))
        if tiles is not None:
            h = _interior_held(up, gate, held.rows, tiles)
        elif gate is None:
            h = jnp.square(jax.nn.relu(up))
        else:
            h = jax.nn.silu(gate) * up
        return jax.lax.ragged_dot(h, w_down, group_sizes)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _combine_weighted(out_per_choice, weights, order, inv, dtype, held=None):
    """Per-(token, choice) expert outputs [n, D] in expert order back to
    token order, weighted by ``weights`` [t, k] — the combine tail both
    ragged lowerings share. The k rows of a token are gathered (``inv``)
    and contracted with its weights in float32. ``held`` (``Held``, None
    = all): a pair whose expert is not here comes with weight 0 (the
    caller's select, which also drops its weight's cotangent), and its
    row, which no expert wrote, is not read into the sum; where the row
    kernel runs (``Held.tiles``) only the held rows are read at all,
    from the prefix of the expert order they fill."""
    t, k = weights.shape
    with jax.named_scope("moe.combine"):
        tiles = None if held is None else held.tiles(t, out_per_choice, dtype)
        if tiles is not None:
            return pallas_rows.rows_sum(
                out_per_choice, order // k,
                _held_weights(weights, order, held.rows), held.rows, t,
                dtype, tiles,
            )
        picked = _rows(out_per_choice, inv).reshape(t, k, -1)
        if held is not None:
            picked = jnp.where(held.mask.reshape(t, k, 1), picked, 0)
        return jnp.einsum(
            "tkd,tk->td", picked, weights,
            preferred_element_type=jnp.float32,
        ).astype(dtype)


def _combine_fwd(out_per_choice, weights, order, inv, dtype, held=None):
    out = _combine_weighted(out_per_choice, weights, order, inv, dtype, held)
    return out, (out_per_choice, weights, order, inv, held)


def _combine_bwd_held(g, out_per_choice, weights, order, held_rows):
    """``_combine_bwd`` over the held prefix of the expert order alone:
    (d_out [n, D], zero behind the prefix's last chunk; d_weights [t, k],
    zero at every pair that is not held). A loop of ``held_rows /
    HELD_CHUNK`` turns (rounded up) around the compiler's own gather: a
    turn takes a chunk of pairs, their tokens' rows of ``g`` and their
    weights, writes the chunk of ``d_out`` and scatters the chunk's row
    dots to their pairs — t·k scalars gathered by ``inv`` cost more
    than the rows."""
    n, d = out_per_choice.shape
    t, k = weights.shape
    chunk = min(HELD_CHUNK, n)
    flat = weights.reshape(-1).astype(jnp.float32)

    def turn(c, carry):
        d_out, d_w = carry
        at = _chunk_start(c, chunk, n)
        pairs = jax.lax.dynamic_slice_in_dim(order, at, chunk)
        g_rows = _rows(g, pairs // k).astype(jnp.float32)
        rows = (g_rows * _rows(flat, pairs)[:, None]).astype(d_out.dtype)
        out_rows = jax.lax.dynamic_slice_in_dim(out_per_choice, at, chunk)
        dots = (out_rows.astype(jnp.float32) * g_rows).sum(-1)
        # a select: what the experts left behind the prefix is garbage
        dots = jnp.where(at + jnp.arange(chunk) < held_rows, dots, 0)
        return (
            jax.lax.dynamic_update_slice_in_dim(d_out, rows, at, 0),
            d_w.at[pairs].set(
                dots, mode="promise_in_bounds", unique_indices=True
            ),
        )

    d_out, d_w = jax.lax.fori_loop(
        0, _prefix_turns(held_rows, chunk), turn,
        (
            jnp.zeros((n, d), out_per_choice.dtype),
            jnp.zeros((t * k,), jnp.float32),
        ),
    )
    return d_out, d_w.reshape(t, k)


def _combine_bwd(dtype, res, g):
    # in expert order, so that d_out feeds the grouped matmul's transpose
    # as it is; only the t·k scalars of d_weights are permuted
    out_per_choice, weights, order, inv, held = res
    with jax.named_scope("moe.combine"):
        if held is not None:
            d_out, d_weights = _combine_bwd_held(
                g, out_per_choice, weights, order, held.rows
            )
            return d_out, d_weights.astype(weights.dtype), None, None, None
        g_sorted = _rows(g, order // weights.shape[1]).astype(jnp.float32)
        w_sorted = _rows(weights.reshape(-1), order)
        d_out = (g_sorted * w_sorted[:, None]).astype(out_per_choice.dtype)
        d_w = (out_per_choice.astype(jnp.float32) * g_sorted).sum(-1)
        d_weights = _rows(d_w, inv).reshape(weights.shape)
    return d_out, d_weights.astype(weights.dtype), None, None, None


_combine_weighted.defvjp(_combine_fwd, _combine_bwd)


def _ragged_ffn(xl, moe_local, gate_idx, weights, dtype, one_device=False):
    """Grouped-GEMM expert FFN over one rank's token slice.

    xl: [T, D] tokens, gate_idx/weights: [T, k] routing. Sorts the (token,
    choice) pairs by expert, runs the experts over them
    (``_ragged_experts``), and sums each token's k weighted expert
    outputs (``_combine_weighted``). No capacity, no drops. Where fewer
    experts are here than the router is wide, ``gate_idx`` is local to
    them and a choice of an expert elsewhere adds nothing
    (``_sort_by_expert``); the combine and both derivatives then go by
    the rows the experts here received (``Held``). ``one_device``: no
    mesh around the call.
    Returns (out [T, D], group_sizes [E here] int32).
    """
    e = moe_local["w_up"].shape[0]
    some_elsewhere = e < moe_local["w_gate"].shape[-1]
    flat_idx, order, inv, sorted_in, group_sizes = _sort_by_expert(
        xl, gate_idx, e, some_elsewhere, one_device
    )
    held = None
    if some_elsewhere:
        held = Held(flat_idx < e, group_sizes.sum(), one_device)
        weights = jnp.where(held.mask.reshape(weights.shape), weights, 0)
    w_gate_proj = moe_local.get("w_gate_proj")
    out_sorted = _ragged_experts(
        sorted_in,
        moe_local["w_up"].astype(dtype),
        None if w_gate_proj is None else w_gate_proj.astype(dtype),
        moe_local["w_down"].astype(dtype),
        group_sizes,
        held,
    )  # [T·k, D], or the rows that can hold a held pair
    out = _combine_weighted(out_sorted, weights, order, inv, dtype, held)
    return out, group_sizes


def _ragged_aux(gate_logits, probs, group_sizes, pmean_axes=None):
    """Router losses from actual (dropless) assignment counts.

    lb loss: E · Σ_e f_e·p_e with f_e = fraction of (token, choice) slots
    routed to e — the dropless analog of GShard's dispatch fraction.
    Global statistics: fractions are pmean'd over token-sharding axes
    BEFORE the product (see _moe_block_alltoall note on bias).

    ``moe_max_load``: rows of the fullest expert over the mean rows an
    expert gets (1 = balanced, E/k = every token picks the same k)."""
    total = jnp.maximum(group_sizes.sum(), 1).astype(jnp.float32)
    frac_tokens = group_sizes.astype(jnp.float32) / total
    frac_probs = probs.astype(jnp.float32).mean(axis=(0, 1))
    z = router_z_loss(gate_logits)
    if pmean_axes:
        frac_tokens = jax.lax.pmean(frac_tokens, axis_name=pmean_axes)
        frac_probs = jax.lax.pmean(frac_probs, axis_name=pmean_axes)
        z = jax.lax.pmean(z, axis_name=pmean_axes)
    e = probs.shape[-1]
    return {
        "moe_lb_loss": e * jnp.sum(frac_tokens * frac_probs),
        "moe_z_loss": z,
        "moe_max_load": e * jnp.max(frac_tokens),
    }


def _ragged_tokens(xl, moe_local, cfg, rng, pmean_axes=None):
    """One rank's token slice [b, s, D] through router, experts and
    combine → (out [b·s, D], aux, expert ids [b, s, k]). Where the
    device holds a part of the experts (``cfg.n_experts_held``) the
    router's statistics still run over all of them, and
    ``moe_held_rows`` counts the rows the experts here received."""
    bl, sl, d = xl.shape
    gate_logits, probs, weights, gate_idx = _route(xl, moe_local, cfg, rng)
    # ids local to the experts here; one outside them is elsewhere
    local_idx = gate_idx
    if cfg.n_experts_held:
        local_idx = gate_idx - cfg.expert_offset
    rows = xl.reshape(bl * sl, d)
    latent = moe_local.get("latent")
    if latent is not None:
        # the experts' rows are the latent's width from dispatch to
        # combine; the router above and the shared expert read ``xl``
        with jax.named_scope("moe.latent"):
            rows = rows @ latent["w_down"].astype(xl.dtype)
    out, group_sizes = _ragged_ffn(
        rows,
        moe_local,
        local_idx.reshape(bl * sl, -1),
        weights.reshape(bl * sl, -1),
        xl.dtype,
        one_device=pmean_axes is None,
    )
    if latent is not None:
        # once, after the combine: on the sum of the experts held here
        with jax.named_scope("moe.latent"):
            out = out @ latent["w_up"].astype(xl.dtype)
    if not cfg.n_experts_held:
        return out, _ragged_aux(
            gate_logits, probs, group_sizes, pmean_axes
        ), gate_idx
    chosen = jnp.sum(
        gate_idx.reshape(-1, 1) == jnp.arange(cfg.n_experts), axis=0,
        dtype=jnp.int32,
    )
    aux = _ragged_aux(gate_logits, probs, chosen, pmean_axes)
    held_rows = group_sizes.sum().astype(jnp.float32)
    if pmean_axes:
        held_rows = jax.lax.pmean(held_rows, axis_name=pmean_axes)
    aux["moe_held_rows"] = held_rows
    return out, aux, gate_idx


def _moe_block_ragged(x, moe, cfg, mesh=None, rng=None):
    """Dropless MoE: per-rank token sort + ragged grouped-GEMM.

    Token-sharding axes (dp/fsdp/sp) stay sharded — each rank routes and
    computes its own token slice with every expert's weights; the expert
    FFN width shards over tp (partial products psum'd). The ``ep`` axis
    is not used by this lowering (experts are token-local); meshes with
    ep>1 route expert WEIGHT storage over ep via the all-to-all/dense
    paths instead.
    """
    b, s, d = x.shape
    if mesh is None or all(
        mesh.shape.get(a, 1) == 1
        for a in ("dp", "fsdp", "sp", "tp", "ep")
    ):
        out, aux, gate_idx = _ragged_tokens(x, moe, cfg, rng)
        aux["moe_choices"] = gate_idx
        return out.reshape(b, s, d), aux

    if cfg.moe_latent_size or cfg.act == "relu2":
        raise ValueError(
            "experts in a latent or without a gate run on one device's "
            "tokens and experts; the sharded ragged lowerings hand their "
            "bodies three SwiGLU matrices"
        )
    if mesh.shape.get("ep", 1) > 1:
        if cfg.n_experts_held:
            raise ValueError(
                "n_experts_held is one device's share of an expert-"
                "parallel layer; an ep mesh shards the experts itself"
            )
        return _moe_block_ragged_a2a(x, moe, cfg, mesh, rng)

    from jax.sharding import PartitionSpec as P

    token_axes = ("dp", "fsdp")

    def body(xl, w_gate, w_up, w_gp, w_down):
        local = {
            "w_gate": w_gate,
            "w_up": w_up,
            "w_gate_proj": w_gp,
            "w_down": w_down,
        }
        out, aux, gate_idx = _ragged_tokens(
            xl, local, cfg, rng, pmean_axes=token_axes + ("sp",)
        )
        # tp shards the FFN width: the down-projection emits partial
        # sums over the mlp dimension
        if mesh.shape.get("tp", 1) > 1:
            out = jax.lax.psum(out, axis_name="tp")
        return out.reshape(xl.shape), aux, gate_idx

    out, aux, choices = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(
            P(token_axes, "sp", None),
            P(None, None),          # router replicated
            P(None, None, "tp"),    # FFN width over tp
            P(None, None, "tp"),
            P(None, "tp", None),
        ),
        out_specs=(
            P(token_axes, "sp", None), P(), P(token_axes, "sp", None)
        ),
        check_vma=False,
    )(
        x,
        moe["w_gate"].astype(x.dtype),
        moe["w_up"].astype(x.dtype),
        moe["w_gate_proj"].astype(x.dtype),
        moe["w_down"].astype(x.dtype),
    )
    aux["moe_choices"] = choices
    return out, aux


def _moe_block_ragged_a2a(x, moe, cfg, mesh, rng):
    """Dropless-by-default expert parallelism: bounded all-to-all for
    bytes, ragged grouped-GEMM for FLOPs.

    The TPU answer to the reference's grouped-GEMM MoE under expert
    parallelism (grouped_gemm_moe.py:46 + moe_layer.py _AllToAll).
    XLA:CPU cannot run `ragged-all-to-all`, and static shapes are the
    XLA contract anyway — so the exchange is a REGULAR all_to_all over
    a per-destination buffer bound (cfg.moe_a2a_bound × the balanced
    share t·k/ep; `ep` ⇒ guaranteed dropless), while the expert compute
    is `lax.ragged_dot` over the ACTUAL received token counts. Unlike
    the capacity path, imbalance costs zero extra FLOPs and tokens only
    drop past the byte bound (counted, not silent: see the
    moe_dropped_frac aux).

    Layout: tokens sharded over (dp, fsdp, ep); experts sharded over ep
    (each rank owns E/ep experts, all its FFN weights local).
    """
    from jax.sharding import PartitionSpec as P

    ep = mesh.shape["ep"]
    e = cfg.n_experts
    if e % ep:
        raise ValueError(f"n_experts {e} not divisible by ep {ep}")
    e_local = e // ep
    b, s, d = x.shape
    token_axes = ("dp", "fsdp", "ep")

    def body(rank, xl, w_gate, w_up, w_gp, w_down):
        local = {
            "w_gate": w_gate,
            "w_up": w_up,
            "w_gate_proj": w_gp,
            "w_down": w_down,
        }
        bl, sl, _ = xl.shape
        gate_logits, probs, weights, gate_idx = _route(xl, local, cfg, rng)
        k = gate_idx.shape[-1]
        t = bl * sl
        cap = max(1, int(cfg.moe_a2a_bound * t * k / ep))
        flat_idx, order, inv, sorted_in, counts = _sort_by_expert(
            xl.reshape(t, d), gate_idx.reshape(t, k), e
        )

        # ---- pack per-destination blocks [ep, cap, D] -------------------
        cnt_dest = counts.reshape(ep, e_local).sum(-1)   # [ep]
        start_dest = jnp.concatenate(
            [jnp.zeros((1,), jnp.int32), jnp.cumsum(cnt_dest)[:-1]]
        )
        slot = jnp.arange(cap)[None, :]                   # [1, cap]
        src_idx = start_dest[:, None] + slot              # [ep, cap]
        send_valid = slot < cnt_dest[:, None]             # drops past cap
        send = jnp.where(
            send_valid[..., None],
            jnp.take(
                sorted_in, jnp.clip(src_idx, 0, t * k - 1), axis=0
            ),
            0.0,
        )                                                  # [ep, cap, D]

        # ---- exchange ---------------------------------------------------
        # axis 0: destination before the a2a, source after
        recv = jax.lax.all_to_all(
            send, "ep", split_axis=0, concat_axis=0, tiled=True
        )                                                  # [ep, cap, D]
        counts_all = jax.lax.all_gather(counts, "ep")      # [ep, E]
        # ep rank from an ep-sharded iota input, not lax.axis_index:
        # partial-manual shard_map on jax 0.4.x lowers axis_index to a
        # PartitionId the SPMD partitioner rejects
        my_rank = rank[0]
        # per (source, local expert) counts for MY experts
        mine = jax.lax.dynamic_slice_in_dim(
            counts_all, my_rank * e_local, e_local, axis=1
        )                                                  # [ep, e_local]
        # also bound by cap: a source sent at most cap of them
        sent_mine = jnp.minimum(
            mine,
            jnp.maximum(
                cap
                - jnp.concatenate(
                    [
                        jnp.zeros((ep, 1), jnp.int32),
                        jnp.cumsum(mine, axis=1)[:, :-1],
                    ],
                    axis=1,
                ),
                0,
            ),
        )

        # ---- compact + sort received rows by expert ---------------------
        # within a source block, rows are expert-sorted; slot b belongs
        # to local expert searchsorted(cumsum(sent_mine[i]), b, 'right')
        csum = jnp.cumsum(sent_mine, axis=1)               # [ep, e_local]
        # padding slots (b >= csum[-1]) get key e_local from searchsorted
        # itself, so they stably sort last — no explicit sentinel needed
        key = jax.vmap(
            lambda c: jnp.searchsorted(c, jnp.arange(cap), side="right")
        )(csum)                                            # [ep, cap]
        perm = jnp.argsort(key.reshape(-1))                # [ep·cap]
        flat_recv = recv.reshape(ep * cap, d)
        compact = jnp.take(flat_recv, perm, axis=0)
        group_sizes = sent_mine.sum(0)                     # [e_local]

        # ---- ragged expert FFN ------------------------------------------
        out_sorted = _ragged_experts(
            compact, w_up, w_gp, w_down, group_sizes
        )
        # zero the sentinel tail so the return path carries no garbage
        n_real = group_sizes.sum()
        out_sorted = jnp.where(
            (jnp.arange(ep * cap) < n_real)[:, None], out_sorted, 0.0
        )

        # ---- return path: unsort, a2a back, unpack ----------------------
        back = jnp.take(
            out_sorted, jnp.argsort(perm), axis=0
        ).reshape(ep, cap, d)
        ret = jax.lax.all_to_all(
            back, "ep", split_axis=0, concat_axis=0, tiled=True
        )                                                  # [ep(dest), cap, D]
        # sorted position p lived in dest block (expert(p)//e_local) at
        # slot p - start_dest[dest]
        pos = jnp.arange(t * k)
        sorted_expert = jnp.take(flat_idx, order)  # order is a permutation
        dest = sorted_expert // e_local
        b_slot = pos - jnp.take(start_dest, dest)
        kept = b_slot < cap
        gathered = ret.reshape(ep * cap, d)[
            jnp.clip(dest * cap + b_slot, 0, ep * cap - 1)
        ]
        out_per_choice = jnp.where(kept[:, None], gathered, 0.0)
        out = _combine_weighted(
            out_per_choice, weights.reshape(t, k), order, inv, jnp.float32
        )

        # ---- aux: global stats ------------------------------------------
        aux = _ragged_aux(
            gate_logits, probs, counts, pmean_axes=token_axes
        )
        dropped = (t * k) - cnt_dest.clip(max=cap).sum()
        aux["moe_dropped_frac"] = jax.lax.pmean(
            dropped.astype(jnp.float32) / (t * k), axis_name=token_axes
        )
        return out.reshape(bl, sl, d).astype(xl.dtype), aux, gate_idx

    out, aux, choices = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(
            P("ep"),
            P(token_axes, None, None),
            P(None, None),          # router replicated
            P("ep", None, None),    # expert-sharded FFN weights
            P("ep", None, None),
            P("ep", None, None),
        ),
        out_specs=(
            P(token_axes, None, None), P(), P(token_axes, None, None)
        ),
        check_vma=False,
    )(
        jnp.arange(ep, dtype=jnp.int32),
        x,
        moe["w_gate"].astype(x.dtype),
        moe["w_up"].astype(x.dtype),
        moe["w_gate_proj"].astype(x.dtype),
        moe["w_down"].astype(x.dtype),
    )
    aux["moe_choices"] = choices
    return out, aux
