"""A chip belongs to one process at a time, and that process is the
worker: the launcher's agent must never initialise a jax backend, the
chip smoke must refuse to run without a chip, and every process must
resolve the same compile-cache directory."""

import os
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_AGENT_SCRIPT = """
import sys

from dlrover_tpu.agent import launcher
from dlrover_tpu.agent.monitor import ResourceMonitor


class Client:
    def report_resource_stats(self, **kw):
        self.sent = kw
        return True


assert launcher._local_chips(launcher.parse_args(["--nproc", "4"])) == 4
assert launcher._local_chips(launcher.parse_args([])) == 2  # the env's
client = Client()
assert ResourceMonitor(client).report_once()
assert set(client.sent) == {"cpu_percent", "used_memory_mb"}, client.sent
# the whole agent, end to end: local master, registration, the comm-perf
# pre-flight (its device work in a child that has exited before the
# worker starts), rendezvous, a worker that exits 0
rc = launcher.main(
    ["--nnodes", "1", "--monitor-interval", "0.2", "--comm-perf-test",
     "--", sys.executable, "-c", "pass"]
)
assert rc == 0, rc
# a pre-flight child that dies is logged, never fatal: it is a diagnostic
launcher.os.environ["JAX_PLATFORMS"] = "no-such-platform"
launcher._run_comm_perf_test()
from jax._src import xla_bridge

assert not xla_bridge.backends_are_initialized()
print("agent stayed off the device")
"""


def _env(**extra):
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    env.update(extra)
    return env


@pytest.mark.parametrize("tracing", ["off", "on"])
def test_agent_never_initialises_a_backend(tmp_path, tracing):
    """With tracing on the agent's spans are mirrored into the profiler's
    annotations (its packages import jax): still no backend comes up."""
    extra = {}
    if tracing == "on":
        extra["DLROVER_TPU_TRACE_DIR"] = str(tmp_path / "trace")
    proc = subprocess.run(
        [sys.executable, "-c", _AGENT_SCRIPT],
        env=_env(
            DLROVER_TPU_LOCAL_CHIPS="2",
            DLROVER_TPU_RUN_ID=f"offdev{os.getpid()}{tracing}",
            DLROVER_TPU_SOCK_DIR=str(tmp_path),
            **extra,
        ),
        cwd=REPO, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "agent stayed off the device" in proc.stdout
    if tracing == "on":
        assert any(
            name.startswith("trace-") for name in os.listdir(extra[
                "DLROVER_TPU_TRACE_DIR"
            ])
        )


def test_chip_smoke_refuses_the_cpu():
    t0 = time.monotonic()
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env=_env(), cwd=REPO, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "needs a TPU" in proc.stderr
    # the probe, not a phase: nothing heavy started
    assert "phase train" not in proc.stdout
    assert time.monotonic() - t0 < 60


_CACHE_SCRIPT = """
from dlrover_tpu.common import compile_cache
print(compile_cache.compile_cache_dir())
print(compile_cache.compile_cache_dir("/job/configured"))
"""


def _resolved(env):
    out = subprocess.run(
        [sys.executable, "-c", _CACHE_SCRIPT], env=env, cwd=REPO,
        capture_output=True, text=True, timeout=60, check=True,
    ).stdout.split()
    return out


def test_compile_cache_dir_is_placed_from_outside():
    env = _env()
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    fixed = os.path.join(REPO, ".jax_compile_cache")
    # unset: one fixed path inside the checkout, the same in every
    # process and on every call; a configured directory comes before it
    assert _resolved(env) == [fixed, "/job/configured"]
    assert _resolved(env) == [fixed, "/job/configured"]
    # set: the environment wins over everything, configured included
    env["JAX_COMPILATION_CACHE_DIR"] = "/from/outside"
    assert _resolved(env) == ["/from/outside", "/from/outside"]
