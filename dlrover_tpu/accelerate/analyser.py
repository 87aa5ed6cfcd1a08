"""Static model/plan analysis: parameter counts, memory feasibility.

Reference: atorch auto/analyser/analyser.py:14 (num params, module types)
+ device_context.py (GPU capability/memory). On TPU the analyser can be
exact about sharded memory: bytes = Σ params·dtype / (fsdp·tp shards) etc.,
so infeasible strategies are rejected before any compilation.
"""

from dataclasses import dataclass, replace
from typing import Dict

from dlrover_tpu.common import device
from dlrover_tpu.models import decoder
from dlrover_tpu.models.config import ModelConfig
from dlrover_tpu.accelerate.strategy import AccelerationPlan

_DTYPE_BYTES = {"float32": 4, "bfloat16": 2, "float16": 2, "int8": 1}

# optimizer state slots per param (mu, nu for adam family)
_OPT_SLOTS = {"adamw": 2, "adam": 2, "agd": 3, "sgd": 1, "lion": 1}
# extra slack multiplier on the streamed-offload working-set bound
# (transfer double-buffering of adjacent leaves in the chain)
OFFLOAD_OPT_LEAF_SLACK = 2.0
# legacy whole-tree offload (non-streaming optimizers): the transient
# device working set is unbounded in principle; budget a conservative
# half of the tree (pre-r3 behavior)
OFFLOAD_OPT_WORKING_SET = 0.5


def offload_streams(plan) -> bool:
    """Whether this plan's offload takes the per-leaf streamed path
    (train/optimizer.py streamed_offload_adamw) — must mirror
    dry_runner.build_from_plan's gate."""
    return (
        plan.offload_opt_state
        and plan.optimizer == "adamw"
        and plan.optimizer_state_dtype is None
    )


@dataclass
class AnalysisResult:
    num_params: int
    param_bytes_per_chip: float
    opt_bytes_per_chip: float
    grad_bytes_per_chip: float
    act_bytes_per_chip: float
    total_bytes_per_chip: float
    flops_per_token: float
    fits: bool
    hbm_bytes: float


def analyse(
    cfg: ModelConfig,
    plan: AccelerationPlan,
    n_devices: int,
    batch_per_chip: int,
    seq: int,
    hbm_bytes: float = 0.0,
) -> AnalysisResult:
    sizes = plan.mesh.resolved_sizes(n_devices)
    n = cfg.num_params()
    pbytes = _DTYPE_BYTES.get(plan.param_dtype, 4)
    param_shards = max(1, sizes["fsdp"] * sizes["tp"] * sizes["pp"])

    param_b = n * pbytes / param_shards
    slots = _OPT_SLOTS.get(plan.optimizer, 2)
    opt_dtype_b = _DTYPE_BYTES.get(
        plan.optimizer_state_dtype or plan.param_dtype, pbytes
    )
    opt_b = n * slots * opt_dtype_b / param_shards
    if (
        getattr(plan, "update_sharding", False)
        and sizes["dp"] > 1
        and sizes["pp"] == 1
        and not plan.offload_opt_state
    ):
        # ZeRO update sharding: each dp rank owns 1/dp of the flattened
        # optimizer state, padded up to whole comm buckets
        # (parallel.sharding.PackPlan). Same gate as
        # resolve_update_sharding — it engages on pure-dp and hybrid
        # dp×fsdp / dp×tp meshes (pp still falls back). On hybrid
        # meshes the flat state is REPLICATED over the model axes and
        # sharded over dp only, so the moments' divisor is dp, not
        # dp × param_shards — fsdp's per-leaf opt sharding is traded
        # for the flat dp shard.
        bucket_b = getattr(plan, "comm_bucket_mb", 4.0) * 2**20
        opt_b = n * slots * opt_dtype_b / sizes["dp"] + slots * bucket_b
    if offload_streams(plan):
        # moments live in pinned host memory and the streamed update
        # (train/optimizer.py streamed_offload_adamw) serializes the
        # per-leaf transfers with optimization_barrier chaining, so the
        # device-resident moment working set is bounded by the LARGEST
        # LEAF's m+v (f32), not a fraction of the tree. Largest leaves:
        # the embedding [vocab, d] and the stacked mlp [L, d, ff].
        d, f, v = cfg.d_model, cfg.d_ff, cfg.vocab_size
        max_leaf = max(v * d, cfg.n_layer * d * f)
        opt_b = (
            OFFLOAD_OPT_LEAF_SLACK * slots * 4 * max_leaf / param_shards
        )
    elif plan.offload_opt_state:
        # non-streaming optimizer on the legacy whole-tree path: no
        # structural bound exists — keep the conservative budget
        opt_b *= OFFLOAD_OPT_WORKING_SET
    grad_b = n * pbytes / param_shards

    act_dtype_b = _DTYPE_BYTES.get(plan.compute_dtype, 2)
    tokens = batch_per_chip * seq
    if plan.remat == "full":
        # only layer-boundary activations are kept
        act_b = tokens * cfg.d_model * act_dtype_b * cfg.n_layer
        if sizes["sp"] == 1:
            # and, at long spans on the flash kernels, the output of
            # every attention layer that keeps it, with its row
            # statistics (one f32 a head)
            kept = decoder.kept_attention_layers(
                replace(cfg, remat="full"), seq
            )
            act_b += tokens * kept * cfg.n_head * (
                cfg.head_dim * act_dtype_b + 4
            )
    else:
        # rough: ~12 activation tensors per layer survive to the backward
        act_b = tokens * cfg.d_model * act_dtype_b * cfg.n_layer * 12
    act_b /= max(1, sizes["tp"] * sizes["sp"])
    # logits in f32 dominate for big vocabs
    act_b += tokens * cfg.vocab_size * 4 / max(1, sizes["tp"])
    if sizes["pp"] > 1:
        # pipeline_apply keeps the full per-stage batch (all microbatches)
        # as fp32 input + output accumulator on every pp stage — these
        # buffers do not shrink with pp
        act_b += 2 * tokens * cfg.d_model * 4

    hbm = hbm_bytes or device.device_memory_bytes()
    total = (param_b + opt_b + grad_b + act_b) * 1.15  # fragmentation slack
    return AnalysisResult(
        num_params=n,
        param_bytes_per_chip=param_b,
        opt_bytes_per_chip=opt_b,
        grad_bytes_per_chip=grad_b,
        act_bytes_per_chip=act_b,
        total_bytes_per_chip=total,
        flops_per_token=cfg.flops_per_token(seq),
        fits=total < hbm * 0.92,
        hbm_bytes=hbm,
    )
