"""Device capability probe.

Reference: atorch's device context (auto/device_context.py:10 — probes
GPU name/memory/compute capability to gate optimizations like fp8 and
flash attention). TPU-native: probe the jax backend once and expose the
facts the strategy search and analyser gate on — HBM size, bf16 peak,
native-fp8 matmul support (Trillium/v6e+), and whether devices share an
ICI domain.
"""

import functools
from dataclasses import dataclass

from dlrover_tpu.common import device
from dlrover_tpu.common.log import get_logger

logger = get_logger(__name__)

# device kinds with native fp8 MXU support (Trillium on)
_FP8_KINDS = ("v6 lite", "v6e", "v7")


@dataclass(frozen=True)
class DeviceContext:
    platform: str          # "tpu" | "cpu" | ...
    device_kind: str       # e.g. "TPU v5 lite"
    n_devices: int
    hbm_bytes: float
    supports_fp8: bool     # native fp8 matmul (not emulated)
    on_tpu: bool


@functools.lru_cache(maxsize=1)
def detect_device_context() -> DeviceContext:
    info = device.device_info()
    on_tpu = info.platform == "tpu"
    ctx = DeviceContext(
        platform=info.platform,
        device_kind=info.device_kind,
        n_devices=info.count,
        hbm_bytes=device.device_memory_bytes(),
        supports_fp8=on_tpu
        and any(k in info.device_kind.lower() for k in _FP8_KINDS),
        on_tpu=on_tpu,
    )
    logger.info("device context: %s", ctx)
    return ctx


def fp8_supported() -> bool:
    return detect_device_context().supports_fp8


# ---------------------------------------------------------------------------
# Kernel capability table
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class KernelCapabilities:
    """One gating table for every hand-written kernel path.

    Every entry derives from the one probe (``common.device``) plus
    the interpret-mode test hook. Consumers: ``decoder`` (fused norm
    auto) and ``ops.fp8._resolve_native`` (native vs bf16-upcast dots).

    ``fp8_native`` means the quantized operands feed the MXU directly;
    False still runs the fp8 recipe with bf16-upcast of the SAME
    quantized values — identical numerics, no speedup (ops/fp8.py).
    """

    flash_attention: bool  # Pallas flash attention kernels usable
    fused_norm: bool       # Pallas fused norm/residual kernels usable
    paged_attention: bool  # fused paged-decode kernel usable (serving)
    fp8_native: bool       # native fp8 MXU dots (else bf16 upcast)
    interpret: bool        # kernels run in Pallas interpret mode


def kernel_capabilities(interpret=None) -> KernelCapabilities:
    """The capability table for this process's backend.

    ``interpret=None`` honors the DLROVER_TPU_PALLAS_INTERPRET test
    hook (kernels execute in interpret mode on CPU); pass True/False
    to force. Cheap: the device probe underneath is lru-cached, the
    rest is module lookups — so callers needn't cache the table and
    env-flipping tests see fresh answers.
    """
    from dlrover_tpu.ops import pallas_norm, pallas_paged

    if interpret is None:
        # the kernel modules all seed from the same env var; norm's
        # copy is authoritative for defaulting
        interpret = pallas_norm.INTERPRET
    ctx = detect_device_context()
    pallas_ok = pallas_norm.kernels_available(interpret)
    return KernelCapabilities(
        flash_attention=pallas_ok,
        fused_norm=pallas_ok,
        paged_attention=pallas_paged.kernels_available(interpret),
        fp8_native=ctx.supports_fp8,
        interpret=bool(interpret) and not ctx.on_tpu,
    )
