"""``run.py`` end to end on the CPU at a tiny preset. The test itself
patches the configuration and the device check (``run.py`` has no
option for it), shows that the last line is the contract's result, and
that the real command refuses to run without a chip."""

import json
import os
import subprocess
import sys

import pytest

from benchmarks.tests import defects

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
TINY_MOE = {
    "source": "test",
    "program": {
        "model": "tiny-moe",
        "overrides": {
            "max_seq": 128, "remat": "full", "vocab_size": 512,
            "attn_block_q": 128, "attn_block_k": 128, "moe_impl": "ragged",
            "moe_aux_coef": 0.01, "moe_z_coef": 0.001,
            # as the chip's recipes: the reference reads the weights
            # the program multiplies by, not a wider copy of them
            "param_dtype": "bfloat16",
        },
        "mesh": {"dp": -1},
        "comm": None,
        "optimizer": {"learning_rate": 1e-4, "warmup_steps": 2,
                      "decay_steps": 100},
    },
    "sizes": {
        "n_layer": 2, "d_model": 128, "n_head": 4, "n_kv_head": None,
        "d_ff": 512, "vocab_size": 512, "max_seq": 128,
        "norm": "rmsnorm", "norm_eps": 1e-6, "act": "swiglu",
        "pos": "rope", "tie_embeddings": True, "attn_window": 0,
        "rope_theta": 10000.0, "n_experts": 4, "expert_top_k": 2,
        "moe_impl": "ragged", "moe_aux_coef": 0.01, "moe_z_coef": 0.001,
    },
    "reference": "moe_plain",
    "check": {"kind": "routed"},
}
DENSE_CHECKS = [
    "logits_vs_reference", "logits_rms_vs_reference", "loss_vs_reference",
    "first_step_loss", "no_compile_in_window", "no_failed_step",
]
ROUTED_CHECKS = [
    "choices_valid", "routing_regret", "logits_vs_reference",
    "logits_rms_vs_reference", "loss_vs_reference",
    "moe_lb_loss_vs_reference", "moe_z_loss_vs_reference",
    "loss_vs_free_reference", "first_step_loss", "no_compile_in_window",
    "no_failed_step",
]
WINDOWED = {
    "n_kv_head": 2, "norm": "rmsnorm", "norm_eps": 1e-6, "act": "swiglu",
    "pos": "rope", "tie_embeddings": False, "attn_window": 64,
}


def _manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


# The routed cases run on a seed whose sound readings sit well inside the
# limits at this size: with 512 tokens in a batch a maximum over tokens
# and a mean router loss swing several times wider from seed to seed
# (regret 0.013..0.083, moe_z_loss 5e-5..1.7e-3 over six seeds) than
# with the chip's 8192, which the limits were set from.
ROUTED_SEED = 2900000033


def _run_patched(monkeypatch, capsys, config, trace, chips=1, choices=None,
                 seed=2**31 + 12345):
    import jax

    from benchmarks import run as bench_run
    from benchmarks.lib import device as devlib
    from benchmarks.lib import peaks, routed
    from benchmarks.tests import choices_tap, moe_plain

    # the routed comparison's reference lives with the tests, and its
    # choices come through the tap while the program has no hook
    monkeypatch.setitem(
        sys.modules, "benchmarks.references.moe_plain", moe_plain
    )
    monkeypatch.setattr(
        routed, "program_logits_and_choices",
        choices or choices_tap.logits_and_choices,
    )

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
        "--workload", cell["name"], "--seed", str(seed),
        "--seconds", "0.5", "--trace", str(trace),
    ])
    captured = capsys.readouterr()
    sys.stderr.write(captured.err)  # left for the caller to read
    return rc, cell, manifest, captured.out.strip().splitlines()


def _events(lines):
    checks, events = {}, {}
    for line in lines[:-1]:
        assert line.startswith("BENCH ")
        record = json.loads(line[6:])
        if record["event"] == "check":
            checks[record["name"]] = record
        else:
            events[record["event"]] = record
    return checks, events


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
    assert list(result) == [
        "correct", "attempted", "failed", "metrics", "device", "checks",
    ]
    assert result["correct"] is True, lines
    assert result["failed"] == 0 and result["attempted"] >= 1
    want = {
        m["name"] for m in manifest["end_to_end"]
        if "workloads" not in m or cell["name"] in m["workloads"]
    }
    assert set(result["metrics"]) == want
    for m in result["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    checks, events = _events(lines)
    # a dense configuration emits the checks of PR 24, by name and order
    assert list(checks) == DENSE_CHECKS
    assert "kind" not in events["reference"]
    assert events["reference"]["shares"] == chips
    assert events["compiled"]["update_sharding"] == (chips > 1)
    assert events["window"]["compiles_in_window"] == 0
    # the result's line ends with each number compared beside its limit
    assert result["checks"] == {
        name: {"value": c["value"], "limit": c["limit"], "ok": True}
        for name, c in checks.items()
    }
    # and so does standard error
    last = capsys.readouterr().err.strip().splitlines()[-len(checks):]
    assert last == [
        f"check {name}: {c['value']!r} against {c['limit']!r}: ok"
        for name, c in checks.items()
    ]


def test_traced_line_reports_what_it_can_read(monkeypatch, capsys):
    from benchmarks import run as bench_run

    runs = []
    read = bench_run.read_layer_metric
    monkeypatch.setattr(
        bench_run, "read_layer_metric",
        lambda name, run: runs.append(run) or read(name, run),
    )
    rc, cell, manifest, lines = _run_patched(monkeypatch, capsys, TINY, 1)
    assert rc == 0
    # readers get the program's step metrics of the warm-up and the
    # traced steps, and no step of the window
    steps = TINY_TRAFFIC["warmup_steps"] + TINY_TRAFFIC["trace_steps"]
    assert {"loss", "tokens", "accuracy"} <= set(runs[0]["step_metrics"])
    for values in runs[0]["step_metrics"].values():
        assert len(values) == steps
        assert all(isinstance(v, float) for v in values)
    result = json.loads(lines[-1])
    names = {m["name"] for m in manifest["per_layer"]}
    assert set(result["metrics"]) <= names
    # host-clock and counter metrics exist without a device trace; the
    # CPU has no device plane, so trace readers return nothing
    assert "train_step.step_ms" in result["metrics"]
    assert "device.idle_share" not in result["metrics"]


def test_routed_configuration_is_correct(monkeypatch, capsys):
    rc, _cell, _manifest, lines = _run_patched(
        monkeypatch, capsys, TINY_MOE, 0, seed=ROUTED_SEED
    )
    assert rc == 0
    checks, events = _events(lines)
    assert json.loads(lines[-1])["correct"] is True, lines
    assert list(checks) == ROUTED_CHECKS
    assert all(c["ok"] for c in checks.values()), checks
    ref = events["reference"]
    assert ref["kind"] == "routed"
    assert len(ref["moved_by_layer"]) == TINY_MOE["sizes"]["n_layer"]
    assert 0 <= ref["regret_max"] <= ref["regret_tol"] < ref["gap_median"]
    # the objective carries the router losses, the reported loss does not
    assert set(ref["program_losses"]) == {"loss", "moe_lb_loss", "moe_z_loss"}


# the objective's terms: whatever scalar the teacher-forced reference
# carries, the program's step metrics have to report under that name
TERM_CASES = {
    # an extra prediction module's cross-entropy, sound on both sides
    "sound": (dict(), True, lambda v: v <= 2e-4),
    # the reference carries it, the program does not report it
    "missing": (dict(factor=None), False, lambda v: isinstance(v, str)),
    # 0.1% off and not listed as a cross-entropy: held at ROUTER_LOSS_TOL,
    # which passes it. Why the class exists: listed, it fails
    # (``defects.ce_term_off``)
    "off_unlisted": (
        dict(factor=1.001, cross_entropy=False), True,
        lambda v: 2e-4 < v <= 2e-3,
    ),
}


@pytest.mark.parametrize("case", sorted(TERM_CASES))
def test_objective_terms_the_reference_carries(monkeypatch, capsys, case):
    kwargs, ok, value_is = TERM_CASES[case]
    defects.extra_prediction(monkeypatch.setattr, **kwargs)
    rc, _cell, _manifest, lines = _run_patched(
        monkeypatch, capsys, TINY_MOE, 0, seed=ROUTED_SEED
    )
    assert rc == 0
    checks, _events_ = _events(lines)
    at = ROUTED_CHECKS.index("moe_z_loss_vs_reference") + 1
    assert list(checks) == (
        ROUTED_CHECKS[:at] + ["mtp_loss_vs_reference"] + ROUTED_CHECKS[at:]
    )
    term = checks["mtp_loss_vs_reference"]
    assert term["ok"] is ok and value_is(term["value"]), term
    assert json.loads(lines[-1])["correct"] is ok
    others = {n: c for n, c in checks.items() if n != "mtp_loss_vs_reference"}
    assert all(c["ok"] for c in others.values()), others


@pytest.mark.parametrize("defect", sorted(defects.INJECT))
def test_routed_comparison_catches(monkeypatch, capsys, defect):
    defects.INJECT[defect](monkeypatch.setattr)
    rc, _cell, _manifest, lines = _run_patched(
        monkeypatch, capsys, TINY_MOE, 0, seed=ROUTED_SEED
    )
    assert rc == 0
    checks, _events_ = _events(lines)
    assert json.loads(lines[-1])["correct"] is False
    failed = {name for name, c in checks.items() if not c["ok"]}
    assert failed & set(defects.CAUGHT_BY[defect]), (defect, checks)
    assert checks["choices_valid"]["ok"]
    # the train step runs the same defect: the step is still the
    # forward-only program's twin
    assert checks["first_step_loss"]["ok"]


def _corrupt(how):
    from benchmarks.tests import choices_tap

    def logits_and_choices(params, tokens, cfg, sizes=None):
        logits, ids = choices_tap.logits_and_choices(params, tokens, cfg)
        if how == "duplicate":
            ids = ids.at[1, 0, 5, 1].set(ids[1, 0, 5, 0])
        else:
            ids = ids.at[0, 1, 9, 0].set(cfg.n_experts)
        return logits, ids

    return logits_and_choices


@pytest.mark.parametrize("how", ["duplicate", "out_of_range"])
def test_choices_that_name_no_experts_fail(monkeypatch, capsys, how):
    rc, _cell, _manifest, lines = _run_patched(
        monkeypatch, capsys, TINY_MOE, 0, choices=_corrupt(how),
        seed=ROUTED_SEED,
    )
    assert rc == 0
    checks, _events_ = _events(lines)
    assert json.loads(lines[-1])["correct"] is False
    assert checks["choices_valid"] == {
        "event": "check", "name": "choices_valid", "ok": False, "value": 1,
        "limit": 0,
    }
    assert "logits_vs_reference" not in checks  # nothing to force


def test_program_without_the_hook_is_refused(monkeypatch, capsys):
    from benchmarks.tests import choices_tap

    rc, _cell, _manifest, lines = _run_patched(
        monkeypatch, capsys, TINY_MOE, 0,
        choices=choices_tap._program_logits_and_choices,
    )
    if rc == 0:  # the program has the hook by now
        assert json.loads(lines[-1])["correct"] is True
        return
    assert rc == 2
    assert lines[-1].startswith("refused:") and "moe_choices" in lines[-1]


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
