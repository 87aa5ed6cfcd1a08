"""Pre-warm the persistent compile cache for candidate re-mesh worlds.

The re-mesh recovery story (SURVEY §7, docs/elastic_training.md): a
SAME-shape restart hits the persistent XLA cache and recompiles
nothing, but the FIRST restart at a new world size pays a full
compile — at real model sizes that alone can blow the <60 s recovery
budget. The reference never faces this (a torch restart recompiles
nothing, elastic_agent/torch/training.py:704); an XLA framework must
pre-pay it.

This module compiles the full train step for each candidate world size
OFF the critical path, ahead of any failure:

- Compilation is **AOT** — ``jit(step).lower(abstract args).compile()``
  over ``jax.ShapeDtypeStruct`` leaves carrying the real shardings — so
  nothing is materialized: pre-warming a 1.5B-param world allocates no
  parameters.
- Each candidate world runs in its own **subprocess** pinned to that
  world's device count (``--xla_force_host_platform_device_count`` on
  the host platform), so the live training backend is never touched.

Call it from the training script at job start (typically
``background=True`` right after the first rendezvous) — the framework
cannot fire it for you, because only the script knows the model and
optimizer configuration the cache keys derive from. The prewarm
children MUST share the workers' cache dir AND platform: cache keys
embed XLA flags and the backend, so host-platform prewarm entries only
serve host-platform jobs. On TPU hosts run the candidates before
training attaches the chips, or accept that only the host-platform
fallback path is warmed.

A warmed cache turns every re-mesh the scaler can produce into the
same-shape-restart case: deserialize, don't compile.
"""

import json
import os
import re
import subprocess
import sys
import threading
from typing import Dict, List, Optional, Sequence

from dlrover_tpu.common import compile_cache
from dlrover_tpu.common.log import get_logger

logger = get_logger(__name__)

_CHILD = """
import json, os, sys
spec = json.loads(os.environ["DLROVER_TPU_PREWARM_SPEC"])
sys.path[:0] = spec["paths"]
import jax
import jax.numpy as jnp

from dlrover_tpu.models import get_config
from dlrover_tpu.parallel import MeshConfig, build_mesh
from dlrover_tpu.train import TrainStepBuilder, make_optimizer
from dlrover_tpu.train.train_step import batch_sharding

cfg = get_config(spec["model"], **spec.get("model_kw", {}))
mesh = build_mesh(MeshConfig.from_dict(spec["mesh"]))
opt = make_optimizer(**spec.get("opt_kw", {"learning_rate": 1e-3}))

# abstract train state: exact shapes AND shardings of the live job's
# init, zero materialization, one trace
from dlrover_tpu.train.train_step import abstract_train_state

state_abs = abstract_train_state(
    cfg, mesh, opt,
    offload_opt_state=spec.get("offload_opt_state", False),
)
b, s = spec["batch_size"], spec["seq"]
bsh = batch_sharding(mesh)
tok = jax.ShapeDtypeStruct((b, s), jnp.int32, sharding=bsh)
batch_abs = {"tokens": tok, "targets": tok}

step = TrainStepBuilder(
    cfg, mesh, opt,
    grad_accum=spec.get("grad_accum", 1),
    attn_impl=spec.get("attn_impl", "auto"),
    offload_opt_state=spec.get("offload_opt_state", False),
).build()
step.lower(state_abs, batch_abs).compile()
print(f"prewarm ok: mesh={spec['mesh']} devices={len(jax.devices())}",
      flush=True)
"""


def prewarm_worlds(
    model: str,
    worlds: Sequence[Dict],
    batch_size: int,
    seq: int,
    *,
    model_kw: Optional[Dict] = None,
    opt_kw: Optional[Dict] = None,
    grad_accum: int = 1,
    attn_impl: str = "auto",
    offload_opt_state: bool = False,
    cache_dir: Optional[str] = None,
    timeout_s: float = 1800.0,
    background: bool = False,
):
    """Compile the train step for each candidate world into the cache.

    ``worlds``: a list of {"n_devices": N, **mesh axis sizes} dicts —
    one subprocess each (sequential, nice'd: pre-warming must never
    contend with live training for cores). ``background=True`` returns
    a started daemon thread instead of blocking.

    Returns the (original) world dicts that compiled successfully (or
    the thread when ``background``).
    """

    def _run() -> List[Dict]:
        ok = []
        for orig_world in worlds:
            world = dict(orig_world)
            n = int(world.pop("n_devices"))
            spec = {
                "model": model,
                "model_kw": model_kw or {},
                "opt_kw": opt_kw or {"learning_rate": 1e-3},
                "mesh": world,
                "batch_size": batch_size,
                "seq": seq,
                "grad_accum": grad_accum,
                "attn_impl": attn_impl,
                "offload_opt_state": offload_opt_state,
                "paths": [p for p in sys.path if p],
            }
            env = dict(os.environ)
            env["DLROVER_TPU_PREWARM_SPEC"] = json.dumps(spec)
            env["JAX_PLATFORMS"] = env.get(
                "DLROVER_TPU_PREWARM_PLATFORM", "cpu"
            )
            # REPLACE (never append) the device-count flag: XLA_FLAGS
            # feeds the persistent-cache key, so a duplicated flag
            # string would silently produce entries the live job's key
            # never matches. Only the host platform honors it — on a
            # real accelerator platform the child can only compile for
            # the devices it actually has, so leave XLA_FLAGS alone
            # (the live job carries none of this flag either).
            if env["JAX_PLATFORMS"] == "cpu":
                flags = re.sub(
                    r"--xla_force_host_platform_device_count=\d+",
                    "",
                    env.get("XLA_FLAGS", ""),
                ).strip()
                env["XLA_FLAGS"] = (
                    flags
                    + f" --xla_force_host_platform_device_count={n}"
                ).strip()
            # the children cache where the workers do: the environment's
            # directory if it names one, else ``cache_dir``, else the
            # fixed in-checkout path (common/compile_cache.py)
            env[compile_cache.ENV] = compile_cache.compile_cache_dir(
                cache_dir or ""
            )
            env["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
            cmd = [sys.executable, "-c", _CHILD]
            if os.name == "posix":
                cmd = ["nice", "-n", "19"] + cmd
            try:
                proc = subprocess.run(
                    cmd,
                    env=env,
                    capture_output=True,
                    text=True,
                    timeout=timeout_s,
                )
            except subprocess.TimeoutExpired:
                logger.warning("prewarm timed out for world %s", world)
                continue
            if proc.returncode == 0:
                logger.info("prewarmed compile cache for world %s", world)
                ok.append(orig_world)
            else:
                logger.warning(
                    "prewarm failed for world %s: %s",
                    world,
                    (proc.stderr or "")[-2000:],
                )
        return ok

    if background:
        t = threading.Thread(target=_run, name="prewarm", daemon=True)
        t.start()
        return t
    return _run()
