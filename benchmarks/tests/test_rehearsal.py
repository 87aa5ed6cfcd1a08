"""``run.py`` end to end on the CPU at a tiny preset. The test itself
patches the configuration and the device check (``run.py`` has no
option for it), shows that the last line is the contract's result, and
that the real command refuses to run without a chip."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)  # the checkout: the real command runs from here

TINY = {
    "source": "test",
    "program": {
        "model": "tiny",
        "overrides": {
            "max_seq": 128, "remat": "full", "norm": "layernorm",
            "act": "gelu", "pos": "learned", "vocab_size": 512,
            "attn_block_q": 128, "attn_block_k": 128,
        },
        "mesh": {"dp": -1},
        "comm": None,
        "optimizer": {"learning_rate": 1e-4, "warmup_steps": 2,
                      "decay_steps": 100},
    },
    "sizes": {
        "n_layer": 2, "d_model": 128, "n_head": 4, "n_kv_head": None,
        "d_ff": 512, "vocab_size": 512, "max_seq": 128,
        "norm": "layernorm", "norm_eps": 1e-5, "act": "gelu",
        "pos": "learned", "tie_embeddings": True, "attn_window": 0,
        "rope_theta": 10000.0,
    },
    "reference": "decoder_plain",
}
TINY_TRAFFIC = {
    "runner": "train", "global_batch": 4, "seq": 128, "warmup_steps": 1,
    "trace_steps": 2, "check": {"q_block": 64},
}
WINDOWED = {
    "n_kv_head": 2, "norm": "rmsnorm", "norm_eps": 1e-6, "act": "swiglu",
    "pos": "rope", "tie_embeddings": False, "attn_window": 64,
}


def _manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _run_patched(monkeypatch, capsys, config, trace, chips=1):
    import jax

    from benchmarks import run as bench_run
    from benchmarks.lib import device as devlib
    from benchmarks.lib import peaks

    manifest = _manifest()
    cell = dict(manifest["workloads"][0], chips=chips)
    manifest = dict(manifest, workloads=[cell])

    def fake_chips(chips):
        devices = jax.devices()[:chips]
        record = {"platform": "cpu-rehearsal", "kind": "cpu", "count": chips}
        return devices, record, peaks.chip_peaks("TPU v5 lite")

    def fake_load(*parts):
        if parts[-1] == "BENCHMARK.json":
            return manifest
        if "traffic" in parts:
            return TINY_TRAFFIC
        return config

    monkeypatch.setattr(devlib, "require_chips", fake_chips)
    monkeypatch.setattr(devlib, "require_kernels", lambda c, what: 0)
    monkeypatch.setattr(bench_run, "load_json", fake_load)
    monkeypatch.setattr(bench_run, "ROOT", str(_scratch(monkeypatch)))
    rc = bench_run.main([
        "--workload", cell["name"], "--seed", str(2**31 + 12345),
        "--seconds", "0.5", "--trace", str(trace),
    ])
    lines = capsys.readouterr().out.strip().splitlines()
    return rc, cell, manifest, lines


def _scratch(monkeypatch):
    import tempfile

    path = tempfile.mkdtemp(prefix="bench_rehearsal_")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", os.path.join(path, "cc"))
    return path


def _zero1():
    cfg = json.loads(json.dumps(TINY))
    cfg["program"]["comm"] = {"update_sharding": "zero1", "bucket_mb": 0.05}
    return cfg


def _windowed():
    cfg = json.loads(json.dumps(TINY))
    cfg["sizes"].update(WINDOWED)
    cfg["program"]["overrides"].update(
        {k: v for k, v in WINDOWED.items() if k != "norm_eps"}
    )
    return cfg


@pytest.mark.parametrize(
    "config,chips",
    [(TINY, 1), (_windowed(), 1), (_zero1(), 4)],
    ids=["gpt2-like", "mistral-like", "zero1-dp4"],
)
def test_end_to_end_line(monkeypatch, capsys, config, chips):
    rc, cell, manifest, lines = _run_patched(
        monkeypatch, capsys, config, 0, chips
    )
    assert rc == 0
    result = json.loads(lines[-1])
    assert set(result) == {"correct", "attempted", "failed", "metrics", "device"}
    assert result["correct"] is True, lines
    assert result["failed"] == 0 and result["attempted"] >= 1
    want = {
        m["name"] for m in manifest["end_to_end"]
        if "workloads" not in m or cell["name"] in m["workloads"]
    }
    assert set(result["metrics"]) == want
    for m in result["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    events = {}
    for line in lines[:-1]:
        assert line.startswith("BENCH ")
        record = json.loads(line[6:])
        events[record["event"]] = record
    assert events["reference"]["shares"] == chips
    assert events["compiled"]["update_sharding"] == (chips > 1)
    assert events["window"]["compiles_in_window"] == 0


def test_traced_line_reports_what_it_can_read(monkeypatch, capsys):
    rc, cell, manifest, lines = _run_patched(monkeypatch, capsys, TINY, 1)
    assert rc == 0
    result = json.loads(lines[-1])
    names = {m["name"] for m in manifest["per_layer"]}
    assert set(result["metrics"]) <= names
    # host-clock and counter metrics exist without a device trace; the
    # CPU has no device plane, so trace readers return nothing
    assert "train_step.step_ms" in result["metrics"]
    assert "device.idle_share" not in result["metrics"]


def test_real_command_refuses_the_cpu():
    manifest = _manifest()
    cell = manifest["workloads"][0]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, *manifest["command"][1:], "--workload",
         cell["name"], "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 2
    last = proc.stdout.strip().splitlines()[-1]
    assert last.startswith("refused:") and "TPU" in last
    assert not last.startswith("{")
