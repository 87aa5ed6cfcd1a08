"""Multi-controller batch formation.

In SPMD multi-host JAX every process must participate in one *global*
batch; each host loads only its data-parallel slice (its shard from the
master's TaskManager) and contributes it as the addressable part of the
global array. Reference analog: the per-worker DataLoader + DistributedSampler
split — here the split is the batch axis sharding itself.

The placement of a batch is also where every loop passes once a step on
the host, the program's own or not: ``form_global_batch`` and
``prefetch_to_device``'s ``put`` tick the process's step clock
(``observability/profiler.py``), once a batch.
"""

import collections
import time
from typing import Any, Dict, Iterable, Iterator, Optional, Tuple

import jax
import numpy as np
from jax.sharding import NamedSharding

from dlrover_tpu.observability.profiler import step_clock


def _ticked(place, batch):
    """``place(batch)`` as one tick of the step clock; a batch the last
    tick placed (it came out of ``form_global_batch`` and now passes
    ``prefetch_to_device``) is not ticked again."""
    clock = step_clock()
    if clock.placed_before(batch):
        return place(batch)
    entered = time.perf_counter()
    placed = place(batch)
    clock.tick(entered, time.perf_counter(), placed)
    return placed


def form_global_batch(
    local_batch: Dict[str, Any], sharding: NamedSharding
) -> Dict[str, Any]:
    """Local per-host arrays → global sharded arrays.

    ``local_batch`` holds this host's rows (global_rows / num_processes).
    Single-process: a plain device_put. Multi-process: every host passes its
    local rows and JAX assembles the global array without any data exchange.
    """
    if jax.process_count() == 1:
        return _ticked(
            lambda batch: jax.device_put(batch, sharding), local_batch
        )

    def put(x):
        x = np.asarray(x)
        global_shape = (x.shape[0] * jax.process_count(),) + x.shape[1:]
        return jax.make_array_from_process_local_data(
            sharding, x, global_shape
        )

    return _ticked(lambda batch: jax.tree.map(put, batch), local_batch)


def prefetch_to_device(
    it: Iterable,
    size: int = 2,
    sharding: Optional[NamedSharding] = None,
) -> Iterator:
    """Keep ``size`` batches in flight to the device ahead of consumption.

    TPU-native analog of the reference's GPU data preloader
    (atorch/atorch/data/preloader.py — cuda-stream H2D overlap):
    ``jax.device_put`` is asynchronous, so enqueueing the NEXT batch's
    transfer before yielding the current one overlaps host→device DMA
    with the running step — no streams, no extra threads. ``sharding``
    places batches directly into their batch sharding (single-process;
    multi-host global batches go through form_global_batch first, whose
    result is already device-resident).
    """
    def put(batch):
        # device_put(x, None) == device_put(x): one helper, both paths
        return _ticked(lambda b: jax.device_put(b, sharding), batch)

    if size <= 0:
        for batch in it:
            yield put(batch)
        return

    queue: collections.deque = collections.deque()

    for batch in it:
        queue.append(put(batch))
        if len(queue) > size:
            yield queue.popleft()
    while queue:
        yield queue.popleft()


def iter_shards_spmd(
    sharding_client, poll_interval_s: float = 2.0
) -> Iterator[Tuple[int, int]]:
    """Lockstep shard iteration for multi-host SPMD.

    In SPMD every process must run the same number of (collective-bearing)
    train steps. A per-process pull from the master's dynamic shard queue
    (reference: sharding/client.py per-worker loop) can desync processes by
    one shard at the end of the dataset, deadlocking the final collectives.
    Here only process 0 talks to the master; each (start, end | done) is
    broadcast so every process sees an identical shard sequence. Each shard
    is one *global* step: callers slice their per-process rows out of
    [start, end).
    """
    if jax.process_count() == 1:
        for start, end, _idx in sharding_client.iter_shards():
            yield start, end
        return

    from jax.experimental import multihost_utils

    while True:
        if jax.process_index() == 0:
            shard = sharding_client.fetch_shard(poll_interval_s)
            msg = np.asarray(
                [0, 0, 1] if shard is None else [shard[0], shard[1], 0],
                dtype=np.int64,
            )
        else:
            msg = np.zeros(3, dtype=np.int64)
        msg = multihost_utils.broadcast_one_to_all(msg)
        if int(msg[2]):
            return
        yield int(msg[0]), int(msg[1])
        if jax.process_index() == 0:
            sharding_client.report_shard_done()
