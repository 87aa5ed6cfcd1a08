"""The process's step clock (``observability/profiler.py``): one tick a
placed batch, the running median, the stall rule and its six causes
from hand-made rings — an injected clock and direct calls of the tick
and the beat, so nothing sleeps — and two cases in real time."""

import collections
import json
import os
import signal
import subprocess
import sys
import threading
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dlrover_tpu.observability import profiler, tracing
from dlrover_tpu.observability.profiler import (
    Beat,
    StepClock,
    reset_step_clock,
    step_clock,
)
from dlrover_tpu.train.data_utils import form_global_batch, prefetch_to_device

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SITE = ("loop.py", "train", 42)
PERIOD = 0.1


@pytest.fixture(autouse=True)
def _fresh():
    reset_step_clock()
    tracing.reset_tracer()
    yield
    reset_step_clock()
    tracing.reset_tracer()


def _quiet_clock(**kw):
    """A clock on hand-made time: no beat thread, no compile recorder,
    no readings of this machine."""
    compiles = types.SimpleNamespace(
        events=collections.deque(), last_backend_end=None
    )
    kw.setdefault("clock", lambda: 0.0)
    return StepClock(
        beat=False, compiles=compiles, readings=lambda tid: {}, **kw
    )


def _steps(clock, t, n, period=PERIOD):
    """``n`` ticks a ``period`` apart, the first at ``t``; the time the
    next is due."""
    for _ in range(n):
        clock.tick(t, t + 0.001)
        t += period
    return t


# ---- one tick a batch ------------------------------------------------------


def _sharding():
    mesh = Mesh(np.array(jax.devices()[:1]), ("dp",))
    return NamedSharding(mesh, P("dp"))


def _host_batches(n):
    return ({"tokens": np.full((2, 8), i, np.int32)} for i in range(n))


@pytest.mark.parametrize("path", ["form_global_batch", "prefetch_to_device", "both"])
def test_one_tick_a_batch(path):
    sharding = _sharding()
    if path == "form_global_batch":
        placed = [form_global_batch(b, sharding) for b in _host_batches(5)]
    elif path == "prefetch_to_device":
        placed = list(prefetch_to_device(_host_batches(5), 2, sharding))
    else:  # the Trainer's multi-host wrap: a batch passes through both
        placed = list(prefetch_to_device(
            (form_global_batch(b, sharding) for b in _host_batches(5)),
            2, sharding,
        ))
    assert [int(b["tokens"][0, 0]) for b in placed] == [0, 1, 2, 3, 4]
    clock = step_clock()
    assert len(clock.ticks) == 5
    assert all(k.place_s > 0 for k in clock.ticks)
    assert clock.ticks[0].interval == 0.0
    assert all(k.interval > 0 for k in list(clock.ticks)[1:])
    # a tick closes a period or is of a burst: never both, never neither
    assert clock.learnt == sum(k.period > 0 for k in clock.ticks) > 0


def test_clock_is_the_process_s_one_and_reset_drops_it():
    clock = step_clock()
    assert step_clock() is clock
    form_global_batch({"x": np.zeros((2, 2))}, _sharding())
    assert clock._thread is not None and clock._thread.is_alive()
    beat = clock._thread
    reset_step_clock()
    assert not beat.is_alive()
    assert step_clock() is not clock and not step_clock().ticks


# ---- the period, the stall rule --------------------------------------------


def test_period_is_the_median_of_the_intervals():
    clock = _quiet_clock()
    t = 100.0
    for period in (0.5, 0.1, 0.1, 0.12, 0.1, 0.1, 0.3, 0.1, 0.1):
        clock.tick(t, t + 0.002)
        t += period
    clock.tick(t, t + 0.002)
    assert clock.learnt == 9
    assert clock.median_s == pytest.approx(0.1)
    assert [k.period for k in clock.ticks] == [0.0] + [
        pytest.approx(k.interval) for k in list(clock.ticks)[1:]
    ]
    seen = clock.window(100.0, t)
    assert seen["ticks"] == 9 and seen["period_s"] == pytest.approx(0.1)
    assert seen["place_s"] == pytest.approx(0.002)
    # the clock sets no counter: nothing would read one
    assert not [n for n in tracing.counters() if n.startswith(("host.", "input."))]
    # it follows a loop that changes its pace
    t = _steps(clock, t + 0.4, 70, period=0.4)
    assert clock.median_s == pytest.approx(0.4)


def _blocks(clock, t, n, k, period=PERIOD, gap=0.001):
    """The Trainer's fused loop (``_train_blockwise``): ``n`` times ``k``
    batches pulled in a burst, ``gap`` apart, then one block of ``k``
    steps; the time the next burst is due."""
    for _ in range(n):
        for i in range(k):
            clock.tick(t + i * gap, t + i * gap + 0.0005)
        t += k * period
    return t


@pytest.mark.parametrize("k", [2, 3, 8])
def test_a_fused_loop_s_period_is_the_block_s(k):
    """K ticks in a burst, then K x the step: the K - 1 intervals inside
    the burst are not the period, and a block is not a stall."""
    clock = _quiet_clock()
    t = _blocks(clock, 10.0, 20, k, period=0.2)
    assert not clock.stalls
    assert clock.median_s == pytest.approx(k * 0.2)
    # the first burst is learnt from tick by tick and forgotten with
    # the first block; from the second burst on, one period a block
    assert clock.learnt == 19
    seen = clock.window(10.0 + (k - 1.5) * 0.001, t)  # from the second burst
    assert seen["ticks"] == 19 * k and seen["stalls"] == 0
    assert seen["period_s"] == pytest.approx(k * 0.2)
    assert seen["place_s"] == pytest.approx(0.0005)
    # a block at 2.9 x is none, one at 3.1 x is a stall, from burst to burst
    t = _blocks(clock, t + 1.9 * k * 0.2, 1, k, period=0.2)
    assert not clock.stalls
    t0 = t - k * 0.2
    t = _blocks(clock, t + 2.1 * k * 0.2, 3, k, period=0.2)
    (record,) = clock.stalls
    assert record["interval_s"] == pytest.approx(3.1 * k * 0.2)
    assert record["excess_s"] == pytest.approx(2.1 * k * 0.2)
    assert record["t"] == pytest.approx(t0 + 3.1 * k * 0.2)
    # the steps behind a stall are not taken for its burst
    closed = [tick for tick in clock.ticks if tick.period > 0]
    assert [tick.period for tick in closed[-3:]] == [
        pytest.approx(3.1 * k * 0.2), pytest.approx(k * 0.2),
        pytest.approx(k * 0.2),
    ]


def test_a_compile_takes_a_quarter_second_of_steps_with_it_at_most():
    """A burst reaches an eighth of the last period, and a quarter of a
    second at most: behind a first step of 30 s the clock learns the
    period from the steps as they come."""
    clock = _quiet_clock()
    clock.tick(10.0, 10.001)
    t = _steps(clock, 40.0, 40, period=0.33)
    assert not clock.stalls and clock.median_s == pytest.approx(0.33)
    assert [k.period for k in clock.ticks][:3] == [
        0.0, pytest.approx(30.0), pytest.approx(0.33)
    ]
    # steps of 0.1 s: two of them, and then one more, go with it
    clock = _quiet_clock()
    clock.tick(10.0, 10.001)
    _steps(clock, 40.0, 40, period=0.1)
    periods = [k.period for k in clock.ticks if k.period > 0]
    assert periods[:3] == [
        pytest.approx(30.0), pytest.approx(0.3), pytest.approx(0.1)
    ]
    assert clock.median_s == pytest.approx(0.1) and clock.learnt == 38


@pytest.mark.parametrize(
    "learnt,period,interval,stalled",
    [
        (7, 0.1, 30.0, False),  # the check after a warm-up: nothing learnt
        (12, 0.2, 0.58, False),  # 2.9 x the median
        (12, 0.2, 0.62, True),  # 3.1 x and over + 0.25 s
        (12, 0.05, 0.2, False),  # 4 x, and under the median + 0.25 s
    ],
)
def test_stall_needs_eight_intervals_three_medians_and_a_quarter_second(
    learnt, period, interval, stalled
):
    clock = _quiet_clock()
    t = _steps(clock, 10.0, learnt + 1, period) - period
    clock.tick(t + interval, t + interval + 0.001)
    assert len(clock.stalls) == int(stalled)
    last = clock.ticks[-1]
    if stalled:
        assert last.excess == pytest.approx(interval - period)
        assert clock.stalls[-1]["excess_s"] == pytest.approx(interval - period)
    else:
        assert last.excess == 0.0


# ---- the six causes, from hand-made rings ----------------------------------


def _rings(clock, t0, t1, late=0.0, late_cpu=0.0, thread_cpu=0.0,
           runqueue=0.0, gc_pause=0.0):
    """Beats every 20 ms over [t0, t1] — but for one gap of ``late``
    seconds in which the process used ``late_cpu`` — with the ticking
    thread's CPU time and run-queue wait advancing by ``thread_cpu`` and
    ``runqueue`` over the interval, and one collection of ``gc_pause``."""
    n = int(round((t1 - t0) / 0.02))
    gap = int(round(late / 0.02))
    pcpu = 50.0
    clock.beats.append(Beat(t0 - 0.02, 0.0, SITE, 7.0, pcpu))
    clock.slow.append({"t": t0 - 0.1, "thread_runqueue_s": 3.0})
    for i in range(1, n + 1):
        if gap and 5 < i < 5 + gap:
            continue  # the beat slept through these
        woke_late = late if gap and i == 5 + gap else 0.0
        pcpu += late_cpu if woke_late else 0.0
        site = SITE if i > 3 else ("other.py", "f", 1)
        clock.beats.append(Beat(
            t0 + 0.02 * i, woke_late, site,
            7.0 + thread_cpu * i / n, pcpu,
        ))
    clock.slow.append({"t": t1 - 0.01, "thread_runqueue_s": 3.0 + runqueue})
    if gc_pause:
        clock.collections.append((t0 + 0.1, gc_pause, 2))


@pytest.mark.parametrize(
    "cause,rings",
    [
        ("gc", dict(late=1.5, late_cpu=1.5, gc_pause=1.5)),
        ("gil_held", dict(late=1.5, late_cpu=1.5)),
        ("process_frozen", dict(late=1.5, late_cpu=0.01)),
        ("main_busy", dict(thread_cpu=1.9)),
        ("main_runnable", dict(runqueue=1.8)),
        ("blocked", dict(thread_cpu=0.01)),
    ],
)
def test_cause_from_the_rings(cause, rings):
    assert cause in profiler.CAUSES
    clock = _quiet_clock()
    clock._compiles.last_backend_end = 5.0
    t0 = _steps(clock, 10.0, 12) - PERIOD
    t1 = t0 + 2.1  # excess 2.0: half of it is 1.0
    clock._compiles.events.extend(
        [(t0 - 1.0, "/jax/before"), (t0 + 1.0, "/jax/inside"),
         (t0 + 1.1, "/jax/inside")]
    )
    _rings(clock, t0, t1, **rings)
    clock.tick(t1, t1 + 0.001)
    (record,) = clock.stalls
    assert record["cause"] == cause
    assert record["interval_s"] == pytest.approx(2.1)
    assert record["median_s"] == pytest.approx(PERIOD)
    assert record["excess_s"] == pytest.approx(2.0)
    assert record["site"] == "loop.py:42 in train"
    assert record["site_share"] > 0.9
    assert record["since_compile_s"] == pytest.approx(t1 - 5.0)
    assert record["events"] == ["/jax/inside"]
    assert record["beat_late_s"] == pytest.approx(rings.get("late", 0.0))
    assert record["gc_s"] == pytest.approx(rings.get("gc_pause", 0.0))
    assert record["thread_cpu_s"] == pytest.approx(
        rings.get("thread_cpu", 0.0), abs=1e-6
    )
    assert record["runqueue_s"] == pytest.approx(rings.get("runqueue", 0.0))
    assert "steal_s" not in record  # nothing read it
    assert "process_cpu_rate" not in record  # one beat before the interval
    json.dumps(record)  # one line of JSON


@pytest.mark.parametrize("woke", [False, True], ids=["asleep", "woke"])
def test_a_beat_still_asleep_at_the_tick_counts_as_late_once(woke):
    """The tick can come before the beat has woken from a freeze: what
    it is late by then is lateness too — but not on top of the wake
    itself, once that is in the ring."""
    clock = _quiet_clock()
    done = threading.Thread(target=lambda: None)
    done.start()
    done.join()
    clock._thread = done  # as with a beat thread running
    t0 = _steps(clock, 10.0, 12) - PERIOD
    # a beat still asleep is held against the process's CPU time of now
    burnt = time.process_time()
    clock.beats.append(Beat(t0 + 0.02, 0.0, SITE, None, burnt))
    clock._beat_due = t0 + 0.04
    if woke:
        clock.beats.append(Beat(t0 + 1.54, 1.5, SITE, None, burnt))
    clock.tick(t0 + 1.6, t0 + 1.601)
    (record,) = clock.stalls
    assert record["cause"] == "process_frozen"
    assert record["beat_late_s"] == pytest.approx(1.5 if woke else 1.56)
    # the ring before the interval: 1.2 CPU seconds in 2 s
    clock.beats.clear()
    t0 += 1.6
    clock.beats.extend([Beat(t0 - 2.0, 0.0, SITE, None, 60.0),
                        Beat(t0 - 0.01, 0.0, SITE, None, 61.194)])
    clock.tick(t0 + 1.6, t0 + 1.601)
    assert clock.stalls[-1]["process_cpu_rate"] == pytest.approx(0.6)


def test_classify_is_the_docstring_s_table():
    rows = [
        # late, gc, late_cpu, thread_cpu, runqueue -> cause
        ((1.0, 1.0, 1.0, 1.0, 1.0), "gc"),
        ((1.0, 0.9, 1.0, 1.0, 1.0), "gil_held"),
        ((1.0, 0.9, 0.9, 1.0, 1.0), "process_frozen"),
        ((0.9, 1.0, 1.0, 1.0, 1.0), "main_busy"),
        ((0.9, 1.0, 1.0, 0.9, 1.0), "main_runnable"),
        ((0.9, 1.0, 1.0, 0.9, 0.9), "blocked"),
    ]
    for args, cause in rows:
        assert profiler.classify(2.0, *args) == cause
    for cause in profiler.CAUSES:
        assert f"``{cause}``" in profiler.__doc__


def test_missing_proc_file_leaves_its_field_out(tmp_path, monkeypatch):
    proc = tmp_path / "proc"
    (proc / "self" / "task" / "77").mkdir(parents=True)
    (proc / "self" / "task" / "77" / "schedstat").write_text(
        "2000000000 500000000 12\n"
    )
    (proc / "stat").write_text(
        "cpu  10 0 10 100 300 0 0 700 0 0\ncpu0 1 2 3\n"
    )
    (proc / "vmstat").write_text(
        "nr_free_pages 1\npgmajfault 9\nallocstall_dma 1\n"
        "allocstall_normal 2\ncompact_stall 4\n"
    )
    monkeypatch.setattr(profiler, "PROC", str(proc))
    monkeypatch.setattr(profiler, "CGROUP", str(tmp_path / "no_cgroup"))
    hz = os.sysconf("SC_CLK_TCK")
    assert profiler.host_readings(77) == {
        "thread_runqueue_s": 0.5, "iowait_s": 300 / hz, "steal_s": 700 / hz,
        "major_faults": 9,
    }  # no cgroup: left out
    # this process's cgroup, v2 and then v1's cpu controller
    cgroup = tmp_path / "cgroup"
    (proc / "self" / "cgroup").write_text("0::/job/worker\n")
    (cgroup / "job" / "worker").mkdir(parents=True)
    (cgroup / "job" / "worker" / "cpu.stat").write_text(
        "usage_usec 5\nnr_throttled 3\nthrottled_usec 1500000\n"
    )
    monkeypatch.setattr(profiler, "CGROUP", str(cgroup))
    assert profiler.host_readings(77)["throttled_s"] == 1.5
    (proc / "self" / "cgroup").write_text(
        "3:memory:/job\n2:cpu,cpuacct:/job\n"
    )
    (cgroup / "cpu,cpuacct" / "job").mkdir(parents=True)
    (cgroup / "cpu,cpuacct" / "job" / "cpu.stat").write_text(
        "nr_throttled 1\nthrottled_time 250000000\n"
    )
    assert profiler.host_readings(77)["throttled_s"] == 0.25
    (proc / "stat").write_text("garbage\n")
    got = profiler.host_readings(77)
    assert "steal_s" not in got and got["major_faults"] == 9
    monkeypatch.setattr(profiler, "PROC", str(tmp_path / "nothing"))
    assert profiler.host_readings(77) == {}


def test_window_leaves_out_a_stall_outside_it():
    clock = _quiet_clock()
    t = _steps(clock, 10.0, 12)  # ticks at 10.0 .. 11.1
    clock.tick(t - PERIOD + 1.0, t - PERIOD + 1.001)  # a stall ends at 12.1
    t = _steps(clock, 12.2, 10)  # 12.2 .. 13.1
    assert len(clock.stalls) == 1
    whole = clock.window(10.0, 13.2)
    assert whole["stalls"] == 1 and whole["stall_s"] == pytest.approx(0.9)
    assert whole["ticks"] == 22
    after = clock.window(12.09, 13.2)  # what a window begun after it sees
    assert after["stalls"] == 0 and after["stall_s"] == 0.0
    assert after["ticks"] == 10
    assert after["period_s"] == pytest.approx(PERIOD)
    assert after["place_s"] == pytest.approx(0.001)
    # a window that ends in a stalled step: no tick has closed it yet
    tail = clock.window(12.09, 13.1 + 0.8)
    assert tail["stalls"] == 1 and tail["stall_s"] == pytest.approx(0.7)
    assert clock.window(12.09, 13.1 + 0.25)["stalls"] == 0
    # an interval begun before the window is not the window's
    assert clock.window(11.5, 13.2)["stalls"] == 0
    assert clock.window(50.0, 60.0) is None


def test_beat_lateness_goes_to_the_tick_that_follows():
    clock = _quiet_clock()
    t = _steps(clock, 10.0, 3)
    clock.beat(now=t - 0.05, due=t - 0.08)  # 30 ms late
    clock.beat(now=t - 0.03, due=t - 0.03)
    clock.tick(t, t + 0.001)
    clock.tick(t + PERIOD, t + PERIOD + 0.001)
    late = [k.beat_late for k in clock.ticks]
    assert late[3] == pytest.approx(0.03) and late[4] == 0.0
    assert clock.window(10.0, t + 1)["beat_late_max_s"] == pytest.approx(0.03)
    assert len(clock.beats) == 2 and clock.beats[0].late == pytest.approx(0.03)


# ---- the span --------------------------------------------------------------


def _on_a_thread(fn, **kw):
    """As the beat runs: not on the thread that ticks."""
    thread = threading.Thread(target=fn, kwargs=kw)
    thread.start()
    thread.join(timeout=10)
    assert not thread.is_alive()


@pytest.mark.parametrize("enabled", [True, False], ids=["tracer", "null"])
def test_host_stall_span(enabled):
    tracer = tracing.configure_tracer("worker") if enabled else tracing.get_tracer()
    assert tracer.enabled is enabled
    clock = _quiet_clock()
    t0 = _steps(clock, 10.0, 12) - PERIOD
    # the beat sees the step overdue, takes the stacks once and opens the span
    _on_a_thread(clock.beat, now=t0 + 0.5, due=t0 + 0.5)
    stacks = clock._open_stacks
    assert stacks and any("test_host_stall_span" in f
                          for frames in stacks.values() for f in frames)
    assert all(len(frames) <= 8 for frames in stacks.values())
    _on_a_thread(clock.beat, now=t0 + 0.52, due=t0 + 0.52)
    assert clock._open_stacks is stacks
    clock.tick(t0 + 1.0, t0 + 1.001)
    assert clock._open_span is None and clock._open_stacks is None
    assert clock.stalls[-1]["stacks"] == stacks
    spans = [e for e in tracer.events() if e["name"] == "host.stall"]
    if not enabled:
        assert spans == []
        return
    (span,) = spans  # the open one was dropped for the record's
    assert span["ph"] == "X" and span["dur"] == pytest.approx(1.0e6)
    assert span["args"]["cause"] == "blocked"
    assert span["args"]["excess_s"] == pytest.approx(0.9)
    assert span["args"]["stacks"] == stacks
    # back-dated onto the tracer's clock: it ends where the tick was
    # taken, by the offset between the two clocks
    end_s = (span["ts"] + span["dur"]) / 1e6
    tick_s = tracer._wall0 + (t0 + 1.0 + clock._mono_offset - tracer._mono0)
    assert end_s == pytest.approx(tick_s, abs=1e-3)


# ---- real time -------------------------------------------------------------


def test_a_sleep_reads_blocked_at_this_line(caplog):
    sharding = _sharding()
    batch = {"tokens": np.zeros((2, 8), np.int32)}
    clock = step_clock()
    profiler.logger.addHandler(caplog.handler)
    try:
        for i in range(14):
            form_global_batch(batch, sharding)
            if i == 11:
                line = sys._getframe().f_lineno + 1
                time.sleep(0.6)
            else:
                time.sleep(0.02)
    finally:
        profiler.logger.removeHandler(caplog.handler)
    (record,) = clock.stalls
    assert record["cause"] == "blocked"
    assert record["site"] == f"{__file__}:{line} in {sys._getframe().f_code.co_name}"
    assert record["site_share"] > 0.8
    assert 0.5 < record["excess_s"] < 0.8
    assert record["thread_cpu_s"] < 0.1 and record["beats"] > 20
    assert record["stacks"]["MainThread"][0] == record["site"]
    lines = [r for r in caplog.records if "host.stall" in r.getMessage()]
    assert len(lines) == 1 and lines[0].levelname == "WARNING"
    assert json.loads(lines[0].getMessage())["cause"] == "blocked"


_CHILD = """
import json, sys, time
sys.path.insert(0, {root!r})
from dlrover_tpu.observability.profiler import StepClock
clock = StepClock()
print("ready", flush=True)
deadline = time.perf_counter() + 20
while not clock.stalls and time.perf_counter() < deadline:
    t = time.perf_counter()
    clock.tick(t, t)
    time.sleep(0.02)
print(json.dumps(list(clock.stalls)), flush=True)
"""


def test_a_stopped_process_reads_process_frozen():
    env = dict(os.environ, JAX_PLATFORMS="cpu", DLROVER_TPU_LOG_LEVEL="ERROR")
    child = subprocess.Popen(
        [sys.executable, "-c", _CHILD.format(root=ROOT)],
        stdout=subprocess.PIPE, text=True, env=env,
    )
    try:
        assert child.stdout.readline().strip() == "ready"
        time.sleep(0.5)  # 8 intervals and more
        os.kill(child.pid, signal.SIGSTOP)
        time.sleep(0.6)
        os.kill(child.pid, signal.SIGCONT)
        out, _ = child.communicate(timeout=30)
    finally:
        child.kill()
    (record,) = json.loads(out.strip().splitlines()[-1])
    assert record["cause"] == "process_frozen"
    assert record["beat_late_s"] > 0.4 and record["late_cpu_s"] < 0.1
    assert 0.4 < record["excess_s"] < 1.0


# ---- the Trainer's seconds, the watchdog -----------------------------------


def test_trainer_reads_last_s_and_a_block_counts_k():
    clock = step_clock()
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((128, 128))
    for _ in range(3):
        clock.start()
        clock.stop(f(x))
    assert clock.steps == 3 and clock.last_s > 0
    clock.stop()  # no interval open: nothing recorded
    assert clock.steps == 3
    # a fused block of K steps is attributed per step
    clock.record(0.8, n_steps=4)
    assert clock.steps == 7 and clock.last_s == pytest.approx(0.2)
    assert not clock.ticks  # the loop's seconds are not ticks


def test_watchdog_names_the_stall_its_step_is_in():
    """The Watchdog sees the slow step before the loop places the next
    batch: the stall is still open, and it is this step's or none."""
    from dlrover_tpu.observability.watchdog import Watchdog, WatchdogConfig

    now = [0.0]
    clock = profiler._clock = _quiet_clock(clock=lambda: now[0])
    t0 = _steps(clock, 10.0, 12) - PERIOD
    wd = Watchdog(WatchdogConfig(min_step_for_drift=0))
    now[0] = t0 + 0.25  # slow by the plan, no stall by the clock's rule
    (rec,) = wd.observe(5, {}, step_time_s=0.25, planned_step_time_s=0.1)
    assert rec.kind == "step_time_regression"
    assert rec.detail.startswith("planned=") and "stall=" not in rec.detail
    _rings(clock, t0, t0 + 2.1, thread_cpu=1.9)
    now[0] = t0 + 2.1
    (rec,) = wd.observe(6, {}, step_time_s=2.1, planned_step_time_s=0.1)
    assert "stall=main_busy excess=2.000s site='loop.py:42 in train'" in rec.detail
    seen = clock.overdue()
    assert seen["open"] is True and seen["interval_s"] == pytest.approx(2.1)
    assert not clock.stalls  # kept, logged and emitted by the tick that ends it
    clock.tick(t0 + 2.2, t0 + 2.201)
    assert clock.stalls[-1]["cause"] == "main_busy" and "open" not in clock.stalls[-1]
    # the stall has passed: a later regression does not name it
    now[0] = t0 + 2.4
    (rec,) = wd.observe(7, {}, step_time_s=0.2, planned_step_time_s=0.1)
    assert "stall=" not in rec.detail


def test_a_failing_record_does_not_reach_the_loop(monkeypatch, caplog):
    """The tick runs inside the placement call of every loop."""
    clock = _quiet_clock()
    t0 = _steps(clock, 10.0, 12) - PERIOD
    monkeypatch.setattr(
        clock, "_gather", lambda *a: (_ for _ in ()).throw(RuntimeError("ring"))
    )
    profiler.logger.addHandler(caplog.handler)
    try:
        clock.tick(t0 + 2.1, t0 + 2.101)
        profiler._clock = clock
        assert profiler.overdue_stall() is None
        clock._clock = lambda: t0 + 9.0
        assert profiler.overdue_stall() is None
    finally:
        profiler.logger.removeHandler(caplog.handler)
    assert clock.ticks[-1].excess == pytest.approx(2.0) and not clock.stalls
    failed = [r.getMessage() for r in caplog.records]
    assert failed == ["step clock: stall record failed", "step clock: overdue failed"]
