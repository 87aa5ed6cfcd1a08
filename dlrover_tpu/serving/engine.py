"""Continuous-batching decode engine over the paged KV cache.

Orca/vLLM-style iteration-level scheduling on a FIXED decode batch of
``n_slots`` lanes: requests are admitted into free slots and evicted at
step boundaries — never mid-step — so the jitted decode step compiles
once and every iteration runs the full batch with a per-lane ``valid``
mask. Each step is:

1. finish: resolve slots that hit ``max_new_tokens``/EOS, free pages;
2. admit: pop queued requests into free slots (head-of-line admission —
   the scheduler's top request waits for pages rather than being jumped);
3. prefill one chunk: ONE slot advances its prompt by ``prefill_chunk``
   tokens per engine step (chunked prefill — long prompts interleave
   with decode instead of stalling the whole batch);
4. decode: one token for every decoding slot in a single jitted call —
   or, with speculative decoding enabled (``spec_k > 0``), one VERIFY
   chunk that can commit up to ``spec_k + 1`` tokens per slot per step.

Per-request sampling is first-class: every ``Request`` carries
``SamplingParams(temperature, top_k, top_p, seed)`` and the fused
in-step sampler draws ``categorical(warp_logits(...))`` with a per-slot
threefry key folded by ABSOLUTE buffer position — deterministic given
the seed and stable across admit/evict reordering and router failover
re-admission (a re-prefilled request re-derives the identical draws).
``temperature=0`` stays the in-graph argmax, bitwise identical to the
historical greedy engine.

Speculative decoding (``spec_k``, prompt-lookup drafts by default):
each decoding slot proposes up to ``spec_k`` continuation tokens from
an n-gram suffix match over its own history (no second model — the
``DraftModel`` hook accepts one), and one jitted verify step scores
``[last token, drafts...]`` against the paged cache with DEFERRED K/V
writes. Acceptance is gumbel-coupled rejection sampling: position j's
target token is drawn exactly as the sequential sampler would draw it,
a draft survives iff it EQUALS that draw, and the first mismatch emits
the target draw — so the output stream is token-for-token the
spec-off stream (exactly the target-model distribution; greedy is the
temperature=0 case). Only the accepted prefix of chunk K/V rows is
committed to the pools — rejected draft rows never reach page storage,
so encode-on-write int8 needs no rollback.

Two decode kernels share the loop (``paged`` ctor flag):

- **paged** (default) — ``decoder.decode_step_paged`` /
  ``prefill_chunk_paged``: steps are ``pools → paged step → pools``.
  K/V rows commit straight to their page cells and attention walks the
  block table (``ops/pallas_paged.py``), so no contiguous
  ``[L, B, S_max, ...]`` cache is ever materialized and per-token KV
  traffic is O(pages held). The page walk is bounded by a power-of-two
  bucket of the max pages any slot holds (a STATIC jit arg — a handful
  of compiles over a slot's lifetime, each reading less of the table).
- **gather** (``paged=False``) — the original
  gather → decode → scatter round trip, kept as the parity reference
  (bf16 outputs are bitwise identical between the two).

The block-table device array is re-shipped only when the allocator
reports a mutation (``consume_dirty``) — steady-state decode steps
reuse the cached device copy.

Prefix sharing (``prefix_sharing=True``): committed prompt pages are
interned into a radix index (``serving/prefix.py``) as chunked prefill
fills them, and admission consults the index — on a hit the new slot's
block-table prefix maps the SAME physical pages (refcounted in the
allocator), prefill resumes at the first divergent chunk boundary, and
a partially-matched tail page is copy-on-write duplicated before the
slot may write into it. Shared pages are read-only through both decode
kernels for free: attention reads via block tables, and every write the
engine issues lands at positions ≥ the resume point, which the plan
keeps strictly above the shared pages. ``admission_lookahead`` lets the
scheduler admit a later request whose (prefix-discounted) footprint
fits past a blocked cold head-of-line request.

Disaggregated prefill/decode (``role`` ctor flag, serving/disagg.py):

- ``unified`` (default) — today's engine, bitwise-unchanged.
- ``prefill`` — chunked prefill ONLY: every prefill slot advances one
  chunk per step (large effective chunk) and the decode/spec batch is
  never traced. Admission reserves a PROMPT-ONLY footprint (the
  generation pages live on the decode replica), and a completed prompt
  leaves through ``handoff_sink`` — fired after each committed chunk
  (``"chunk"``, streaming page shipment overlapped with the next
  chunk's compute), at completion (``"done"``), or when the request
  finishes at its first token with nothing to hand off
  (``"local_done"``).
- ``decode`` — pure batched decode: raw prompts are never
  chunk-prefilled. Work arrives as handoffs (``import_slot`` with a
  staged reservation) or as prefix-affinity admissions whose radix-index
  plan covers all but ``affinity_suffix_max`` trailing prompt tokens
  (the short divergent suffix is the only prefill this engine runs). A
  popped request whose plan degraded is parked on ``bounced`` for the
  router to re-dispatch through the prefill pool.

Alignment invariant: the slot capacity ``S_max`` must be a multiple of
``prefill_chunk``. Chunk starts are always multiples of the chunk width,
and ``lax.dynamic_slice`` CLAMPS out-of-bounds starts — an unaligned
tail window would silently shift the slice and corrupt earlier cache
rows. ``__init__`` enforces it.
"""

import time
from collections import deque
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from dlrover_tpu.common import device
from dlrover_tpu.models import decoder, generate
from dlrover_tpu.observability.tracing import get_tracer
from dlrover_tpu.ops import pallas_paged, quant
from dlrover_tpu.serving import kv_cache as kvc
from dlrover_tpu.serving import prefix as prefix_mod
from dlrover_tpu.serving.scheduler import AdmissionError, Request, Scheduler


class DraftModel:
    """Draft-token proposer hook for speculative decoding.

    ``propose(history, k)`` returns up to ``k`` candidate continuation
    tokens for a slot whose committed stream is ``history``
    (prompt + generated so far). Runs on the host between jitted steps;
    returning ``[]`` makes the slot fall back to plain decode for that
    step. Acceptance is handled by the engine's verify step, so a
    proposer can be arbitrarily wrong without affecting the output
    distribution — only the accept rate."""

    def propose(self, history: Sequence[int], k: int) -> List[int]:
        raise NotImplementedError


class PromptLookupDraft(DraftModel):
    """Prompt-lookup (n-gram) drafting — no second model.

    Finds the most recent EARLIER occurrence of the history's trailing
    n-gram (longest first, ``max_ngram`` down to ``min_ngram``) and
    proposes the tokens that followed it. Input-grounded workloads
    (summarization, code edits, retrieval) repeat long prompt spans
    verbatim, which is exactly what this matches."""

    def __init__(self, max_ngram: int = 3, min_ngram: int = 1):
        if min_ngram < 1 or max_ngram < min_ngram:
            raise ValueError("need max_ngram >= min_ngram >= 1")
        self.max_ngram = max_ngram
        self.min_ngram = min_ngram

    def propose(self, history: Sequence[int], k: int) -> List[int]:
        hist = [int(t) for t in history]
        if k <= 0 or len(hist) < 2:
            return []
        top = min(self.max_ngram, len(hist) - 1)
        for n in range(top, self.min_ngram - 1, -1):
            pat = hist[-n:]
            for i in range(len(hist) - n - 1, -1, -1):
                if hist[i:i + n] == pat:
                    # i + n <= len-1, so there is always >= 1 token here
                    return hist[i + n:i + n + k]
        return []


@dataclass
class _Slot:
    """Host-side state of one decode lane."""

    req: Request
    phase: str                  # "prefill" | "decode" | "handoff"
    prompt: np.ndarray          # int32 [P]
    key_data: np.ndarray        # uint32 [2] — threefry key for sampling
    n_prefilled: int = 0
    generated: List[int] = field(default_factory=list)
    span: object = None         # open "serving.decode" trace span, if any
    interned_pages: int = 0     # full prompt pages already in the trie


class ServingEngine:
    """Single-replica continuous-batching engine (host loop + 2 jits)."""

    def __init__(
        self,
        params,
        cfg,
        scheduler: Scheduler,
        *,
        n_slots: int = 4,
        max_len: int = 128,
        page_size: int = 16,
        mode: str = "int8",
        prefill_chunk: int = 8,
        slack_pages: int = 0,
        paged: bool = True,
        page_bucketing: bool = True,
        spec_k: int = 0,
        draft: Optional[DraftModel] = None,
        prefix_sharing: bool = False,
        admission_lookahead: int = 0,
        role: str = "unified",
        affinity_suffix_max: Optional[int] = None,
    ):
        if role not in ("unified", "prefill", "decode"):
            raise ValueError(
                f"role must be 'unified', 'prefill' or 'decode', got {role!r}"
            )
        self.role = role
        # disaggregation hook (serving/disagg.py): a prefill-role engine
        # calls sink(slot_idx, slot, event) with event "chunk" after
        # every committed chunk, "done" at prefill completion, and
        # "local_done" when the request finished at its first token
        self.handoff_sink = None
        # decode-role bounce lane: popped requests whose prefix-affinity
        # plan no longer qualifies park here for the router to re-dispatch
        # through the prefill pool — a decode-role engine never
        # chunk-prefills a cold prompt
        self.bounced: deque = deque()
        if affinity_suffix_max is None:
            affinity_suffix_max = 2 * prefill_chunk if role == "decode" else 0
        self.affinity_suffix_max = int(affinity_suffix_max)
        self.params = params
        self.cfg = cfg
        self.scheduler = scheduler
        self.n_slots = n_slots
        self.prefill_chunk = prefill_chunk
        self.paged = bool(paged)
        self.page_bucketing = bool(page_bucketing)
        if spec_k < 0:
            raise ValueError(f"spec_k must be >= 0, got {spec_k}")
        self.spec_k = int(spec_k)
        self.draft = draft if draft is not None else PromptLookupDraft()
        self.geom = kvc.make_geometry(
            cfg, n_slots=n_slots, max_len=max_len, page_size=page_size,
            mode=mode, slack_pages=slack_pages,
        )
        if self.geom.max_len % prefill_chunk:
            raise ValueError(
                f"slot capacity {self.geom.max_len} (pages*page_size) must "
                f"be a multiple of prefill_chunk={prefill_chunk}: chunk "
                "starts are chunk-aligned and dynamic_slice clamps "
                "out-of-bounds starts, which would corrupt earlier rows"
            )
        self.alloc = kvc.PageAllocator(self.geom, n_slots)
        self.pools = kvc.init_pools(self.geom)
        self.prefix_sharing = bool(prefix_sharing)
        self.admission_lookahead = int(admission_lookahead)
        self.trie: Optional[prefix_mod.PrefixIndex] = None
        if self.prefix_sharing:
            self.trie = prefix_mod.PrefixIndex(page_size)
            # pages whose refcount hits zero leave the index atomically
            # with their free-list return
            self.alloc.on_free = self.trie.drop_pages
        self.slots: List[Optional[_Slot]] = [None] * n_slots
        self.draining = False     # planned drain: stop admitting new work
        self._tokens = 0
        self._t0: Optional[float] = None
        self._tables_dev = None   # cached device block tables
        self._table_ships = 0     # host→device table transfers
        self._step_time = 0.0     # wall seconds inside jitted steps
        self._draft_tokens = 0    # drafts proposed to the verify step
        self._accepted_tokens = 0  # drafts that survived acceptance
        self._prefill_tokens = 0  # prompt tokens run through the chunk fn
        self._prefill_chunks = 0  # chunk_fn invocations (the compute unit)
        self._migrated_in = 0     # requests adopted as live KV pages
        self._migrated_out = 0    # requests donated as live KV pages
        self._handoffs_in = 0     # disagg handoffs committed into a slot
        self._handoffs_out = 0    # prefilled requests released downstream
        self._handoff_bytes = 0   # wire bytes shipped/staged (both roles)
        self._affinity_bounced = 0  # decode-role pops with a degraded plan
        self._prefix_hits = 0     # admissions that mapped shared pages
        self._prefix_misses = 0   # sharing-on admissions with no usable hit
        self._prefill_tokens_saved = 0  # prompt tokens skipped via hits
        self._cow_pages = 0       # tail pages copy-on-write duplicated
        self._peak_dedup = 1.0    # peak Σ slot cells / unique pages

        self._slack_pages = int(slack_pages)
        self._build_step_fns()

    def _build_step_fns(self) -> None:
        """(Re)build the three jitted step closures from the current
        geometry + knobs. Called at construction and again by
        :meth:`retune` when a value a closure captured changes
        (``prefill_chunk`` is baked into the gather-mode chunk slice;
        the geometry behind ``n_slots`` shapes everything) — a retune
        is a closure rebuild at a step boundary, never a process
        restart, and recompiles lazily on first use."""
        geom = self.geom
        cfg = self.cfg
        paged = self.paged
        chunk_w = self.prefill_chunk

        def _draw_rows(logits, keys, draw_pos, temp, top_k, top_p):
            """Fused per-slot sampler: one token per row of ``logits``
            [B, V], drawn with ``fold_in(slot key, absolute position of
            the token being drawn)`` — the SAME stream the offline
            ``generate.sample`` consumes, which is what pins engine
            sampling against the single-request reference. Greedy rows
            (temperature 0) take the bitwise-pinned argmax."""
            base = jax.random.wrap_key_data(keys)
            draw_keys = jax.vmap(jax.random.fold_in)(base, draw_pos)
            return jax.vmap(generate.draw_token)(
                logits, draw_keys, temp, top_k, top_p
            )

        def _accept_and_emit(logits, tokens, start, valid, n_draft,
                             keys, temp, top_k, top_p):
            """Gumbel-coupled rejection sampling over a verify chunk.

            Row j's logits predict position start+j+1; its target token
            is drawn exactly as the sequential sampler at that position
            would draw it. Draft d_j (chunk row j) survives iff it
            EQUALS the target draw from row j-1, acceptance stops at
            the first mismatch, and the mismatching position emits the
            target draw itself — so the emitted stream is bitwise the
            spec-off stream, and in distribution it is exactly the
            target model's (standard rejection-sampling guarantee for a
            deterministic proposer). Returns (targets [B, C], n_emit
            [B], commit mask [B, C] covering rows 0..n_accepted)."""
            b, c = tokens.shape
            positions = (
                start[:, None] + jnp.arange(c, dtype=jnp.int32)[None, :]
            )
            base = jax.random.wrap_key_data(keys)
            draw_keys = jax.vmap(
                lambda kk, ps: jax.vmap(
                    lambda p: jax.random.fold_in(kk, p)
                )(ps)
            )(base, positions + 1)
            draw = jax.vmap(
                jax.vmap(
                    generate.draw_token, in_axes=(0, 0, None, None, None)
                )
            )
            tgt = draw(logits, draw_keys, temp, top_k, top_p)
            drafts = tokens[:, 1:]
            draft_ok = jnp.arange(c - 1)[None, :] < n_draft[:, None]
            match = (drafts == tgt[:, :-1]) & draft_ok
            n_acc = jnp.cumprod(match.astype(jnp.int32), axis=1).sum(1)
            commit = (
                jnp.arange(c)[None, :] <= n_acc[:, None]
            ) & valid[:, None]
            return tgt, n_acc + 1, commit

        def _as_committed_rows(rows):
            """What a chunk K/V row [B, C, Hkv, D] reads back as AFTER
            a pool commit — the int8 block codec round-trip (bf16
            pools: identity). Keeps gather-mode verify acceptance math
            independent of commit timing."""
            if geom.mode == "bf16":
                return rows
            lead = rows.shape[:2]
            qv, sc = quant.kv_encode_rows(
                rows.reshape(*lead, geom.row_elems), geom.kv_block
            )
            return quant.kv_decode_rows(qv, sc, rows.dtype).reshape(
                rows.shape
            )

        # buffer donation is a no-op (with a warning) on the CPU backend
        donate = () if device.on_cpu() else (1,)

        if paged:

            def decode_fn(params, pools, tables, tokens, pos, valid,
                          keys, temp, top_k, top_p, max_pages):
                """One token for every slot, pools → pools: rows commit
                straight to page cells, attention walks the block table
                (no contiguous-cache gather anywhere in the trace)."""
                logits, pools = decoder.decode_step_paged(
                    params, tokens, pools, tables, pos, valid, cfg,
                    max_pages=max_pages,
                )
                tok = _draw_rows(logits, keys, pos + 1, temp, top_k, top_p)
                return tok, pools

            def chunk_fn(params, pools, tables, tokens, start, chunk_len,
                         keys, temp, top_k, top_p, max_pages):
                """One prefill chunk for ONE slot (batch dim kept at 1),
                pools → pools; token 0 of the continuation drawn at the
                last VALID position (only meaningful on the final
                chunk)."""
                logits, pools = decoder.prefill_chunk_paged(
                    params, tokens, pools, tables, start, chunk_len, cfg,
                    max_pages=max_pages,
                )
                last = jnp.take_along_axis(
                    logits, (chunk_len - 1)[:, None, None], axis=1
                )[:, 0]
                tok = _draw_rows(
                    last, keys, start + chunk_len, temp, top_k, top_p
                )
                return tok, pools

            def verify_fn(params, pools, tables, tokens, start, valid,
                          n_draft, keys, temp, top_k, top_p, max_pages):
                """Speculative verify for every decoding slot: chunk =
                [last token, drafts...]; K/V writes are DEFERRED — the
                paged attention folds the in-flight rows as extra keys,
                and only rows 0..n_accepted commit to the pools after
                the acceptance rule runs. Rejected draft rows never
                reach page storage."""
                logits, ck, cv = decoder.verify_chunk_paged(
                    params, tokens, pools, tables, start, cfg,
                    max_pages=max_pages,
                )
                tgt, n_emit, commit = _accept_and_emit(
                    logits, tokens, start, valid, n_draft,
                    keys, temp, top_k, top_p,
                )
                c = tokens.shape[1]
                positions = (
                    start[:, None] + jnp.arange(c, dtype=jnp.int32)[None, :]
                )

                def wr(_, inp):
                    pools_l, k_l, v_l = inp
                    return None, pallas_paged.write_page_rows(
                        pools_l, tables, positions, commit, k_l, v_l
                    )

                _, pools = jax.lax.scan(wr, None, (pools, ck, cv))
                return tgt, n_emit, pools

        else:

            def decode_fn(params, pools, tables, tokens, pos, valid,
                          keys, temp, top_k, top_p, max_pages):
                """One token for every slot: gather pages → decode_step →
                scatter the new K/V row back (invalid lanes → trash page).
                The parity reference for the paged kernel; the gather is
                sliced to ``max_pages`` held pages."""
                views = kvc.gather(pools, tables, geom, max_pages=max_pages)
                logits, new_cache = decoder.decode_step(
                    params, tokens, views, pos, cfg, prefilled=True
                )
                take = jax.vmap(
                    lambda c, p: jax.lax.dynamic_slice_in_dim(
                        c, p, 1, axis=1
                    )[:, 0],
                    in_axes=(1, 0),
                    out_axes=1,
                )
                rows_k = take(new_cache["k"], pos)[:, :, None]
                rows_v = take(new_cache["v"], pos)[:, :, None]
                pools = kvc.write_rows(
                    pools, tables, pos[:, None], valid[:, None],
                    rows_k, rows_v, geom,
                )
                tok = _draw_rows(logits, keys, pos + 1, temp, top_k, top_p)
                return tok, pools

            def chunk_fn(params, pools, tables, tokens, start, chunk_len,
                         keys, temp, top_k, top_p, max_pages):
                """Gather-mode prefill chunk (see decode_fn above)."""
                views = kvc.gather(pools, tables, geom, max_pages=max_pages)
                logits, new_cache = decoder.prefill_chunk(
                    params, tokens, views, start, cfg
                )
                take = jax.vmap(
                    lambda c, s: jax.lax.dynamic_slice_in_dim(
                        c, s, chunk_w, axis=1
                    ),
                    in_axes=(1, 0),
                    out_axes=1,
                )
                rows_k = take(new_cache["k"], start)
                rows_v = take(new_cache["v"], start)
                positions = (
                    start[:, None] + jnp.arange(chunk_w, dtype=jnp.int32)
                )
                valid = jnp.arange(chunk_w)[None, :] < chunk_len[:, None]
                pools = kvc.write_rows(
                    pools, tables, positions, valid, rows_k, rows_v, geom,
                )
                last = jnp.take_along_axis(
                    logits, (chunk_len - 1)[:, None, None], axis=1
                )[:, 0]
                tok = _draw_rows(
                    last, keys, start + chunk_len, temp, top_k, top_p
                )
                return tok, pools

            def verify_fn(params, pools, tables, tokens, start, valid,
                          n_draft, keys, temp, top_k, top_p, max_pages):
                """Gather-mode verify: no write into the view — each
                chunk row rides as a per-query key (earlier rows
                as-committed through the pool codec, own row raw, the
                sequential loop's exact mix), then only the accepted
                prefix of RAW rows commits back to the pools. Rejected
                draft rows still never reach page storage."""
                c = tokens.shape[1]
                views = kvc.gather(pools, tables, geom, max_pages=max_pages)
                logits, rows_k, rows_v = decoder.verify_chunk(
                    params, tokens, views, start, cfg,
                    as_committed=_as_committed_rows,
                )
                tgt, n_emit, commit = _accept_and_emit(
                    logits, tokens, start, valid, n_draft,
                    keys, temp, top_k, top_p,
                )
                positions = (
                    start[:, None] + jnp.arange(c, dtype=jnp.int32)[None, :]
                )
                pools = kvc.write_rows(
                    pools, tables, positions, commit, rows_k, rows_v, geom,
                )
                return tgt, n_emit, pools

        self._decode_fn = jax.jit(
            decode_fn, donate_argnums=donate, static_argnums=(10,)
        )
        self._chunk_fn = jax.jit(
            chunk_fn, donate_argnums=donate, static_argnums=(10,)
        )
        self._verify_fn = jax.jit(
            verify_fn, donate_argnums=donate, static_argnums=(11,)
        )

    # ---- queries ---------------------------------------------------------

    @property
    def max_len(self) -> int:
        """Longest prompt+generation one slot can hold."""
        return self.geom.max_len

    def active_slots(self) -> int:
        return sum(s is not None for s in self.slots)

    # ---- live retuning ---------------------------------------------------

    def retune(
        self,
        *,
        spec_k: Optional[int] = None,
        prefill_chunk: Optional[int] = None,
        page_bucketing: Optional[bool] = None,
        n_slots: Optional[int] = None,
    ) -> dict:
        """Apply a brain tuning revision (cluster/brain.py TuningPlan
        serving knobs) at a step boundary, without a restart.

        Every knob preserves the bitwise-parity invariants: sampling is
        keyed by ``fold_in(slot key, absolute position)``, so the token
        stream is independent of spec_k (spec-on == spec-off), chunk
        width, page bucketing, and slot count at the same seeds.

        Application classes:

        - ``spec_k`` / ``page_bucketing`` — host-side reads, effective
          on the next step with no rebuild.
        - ``prefill_chunk`` — baked into the gather-mode chunk closure,
          so the step fns are rebuilt. Chunk starts must stay aligned:
          the new width must divide slot capacity AND every in-flight
          prefill's resume point; a misaligned request defers the knob
          (returned under ``"deferred"``) for the caller's next
          boundary rather than corrupting a live slot.
        - ``n_slots`` — sizes the geometry, allocator, pools and block
          tables; applied only when the engine is fully idle (resident
          KV cannot survive a pool reshape). Busy engines defer.

        Returns ``{"applied": {knob: new}, "deferred": {knob: why}}``.
        """
        applied: dict = {}
        deferred: dict = {}
        if spec_k is not None:
            if spec_k < 0:
                raise ValueError(f"spec_k must be >= 0, got {spec_k}")
            if int(spec_k) != self.spec_k:
                self.spec_k = int(spec_k)
                applied["spec_k"] = self.spec_k
        if page_bucketing is not None:
            if bool(page_bucketing) != self.page_bucketing:
                self.page_bucketing = bool(page_bucketing)
                # bucket width changed: the cached device tables were
                # padded to the old bucket
                self._tables_dev = None
                applied["page_bucketing"] = self.page_bucketing
        rebuild = False
        if n_slots is not None and int(n_slots) != self.n_slots:
            n_new = int(n_slots)
            if n_new < 1:
                raise ValueError(f"n_slots must be >= 1, got {n_new}")
            if self.active_slots():
                deferred["n_slots"] = (
                    f"{self.active_slots()} slots hold live KV; pools "
                    "cannot reshape under them"
                )
            else:
                g = self.geom
                self.geom = kvc.make_geometry(
                    self.cfg, n_slots=n_new, max_len=g.max_len,
                    page_size=g.page_size, mode=g.mode,
                    slack_pages=self._slack_pages,
                )
                self.alloc = kvc.PageAllocator(self.geom, n_new)
                self.pools = kvc.init_pools(self.geom)
                self.slots = [None] * n_new
                self.n_slots = n_new
                self._tables_dev = None
                if self.prefix_sharing:
                    # shared pages died with the old pools
                    self.trie = prefix_mod.PrefixIndex(g.page_size)
                    self.alloc.on_free = self.trie.drop_pages
                rebuild = True
                applied["n_slots"] = n_new
        if prefill_chunk is not None and int(prefill_chunk) != self.prefill_chunk:
            pc = int(prefill_chunk)
            if pc < 1:
                raise ValueError(f"prefill_chunk must be >= 1, got {pc}")
            if self.geom.max_len % pc:
                raise ValueError(
                    f"slot capacity {self.geom.max_len} must be a "
                    f"multiple of prefill_chunk={pc} (chunk starts are "
                    "chunk-aligned; dynamic_slice clamps out-of-bounds "
                    "starts)"
                )
            misaligned = [
                i for i, s in enumerate(self.slots)
                if s is not None and s.phase == "prefill"
                and s.n_prefilled % pc
            ]
            if misaligned:
                deferred["prefill_chunk"] = (
                    f"slots {misaligned} mid-prefill at non-multiples "
                    f"of {pc}"
                )
            else:
                self.prefill_chunk = pc
                rebuild = True
                applied["prefill_chunk"] = pc
        if rebuild:
            self._build_step_fns()
        return {"applied": applied, "deferred": deferred}

    def stats(self) -> dict:
        dt = time.monotonic() - self._t0 if self._t0 else 0.0
        return {
            "active_slots": self.active_slots(),
            "free_pages": self.alloc.free_pages,
            "tokens_generated": self._tokens,
            "tokens_per_s": self._tokens / dt if dt > 0 else 0.0,
            "decode_kernel": "paged" if self.paged else "gather",
            "table_ships": self._table_ships,
            "step_time_s": self._step_time,
            "host_time_s": max(0.0, dt - self._step_time),
            "spec_k": self.spec_k,
            "draft_tokens": self._draft_tokens,
            "accepted_tokens": self._accepted_tokens,
            "spec_accept_rate": (
                self._accepted_tokens / self._draft_tokens
                if self._draft_tokens else 0.0
            ),
            # migration accounting: the drill's zero-re-prefill assertion
            # reads prefill_tokens before/after a failover
            "prefill_tokens": self._prefill_tokens,
            "prefill_chunks": self._prefill_chunks,
            "migrated_in": self._migrated_in,
            "migrated_out": self._migrated_out,
            # disaggregation: replica role and handoff accounting
            # (serving/disagg.py mutates the byte counter from its pump
            # thread — telemetry-grade, not a synchronization point)
            "role": self.role,
            "handoffs_in": self._handoffs_in,
            "handoffs_out": self._handoffs_out,
            "handoff_bytes": self._handoff_bytes,
            "affinity_bounced": self._affinity_bounced,
            # prefix sharing: hit rate over sharing-on admissions, prompt
            # tokens whose prefill was skipped, COW duplications, live
            # trie size, and the dedup ratio (slot cells per unique
            # physical page — 1.0 means nothing is shared)
            "prefix_hit_rate": (
                self._prefix_hits / (self._prefix_hits + self._prefix_misses)
                if (self._prefix_hits + self._prefix_misses) else 0.0
            ),
            "prefix_hits": self._prefix_hits,
            "prefix_misses": self._prefix_misses,
            "prefill_tokens_saved": self._prefill_tokens_saved,
            "cow_pages": self._cow_pages,
            "trie_pages": (
                self.trie.n_pages if self.trie is not None else 0
            ),
            "dedup_ratio": self.dedup_ratio(),
            "peak_dedup_ratio": self._peak_dedup,
        }

    def dedup_ratio(self) -> float:
        """Σ slot cells / unique assigned pages — how many logical pages
        each resident physical page serves (resident-bytes dedup)."""
        unique = self.alloc.unique_assigned_pages
        if not unique:
            return 1.0
        cells = sum(self.alloc.slot_pages(i) for i in range(self.n_slots))
        return cells / unique

    def resident_kv_bytes(self) -> int:
        return kvc.resident_bytes(self.geom)

    def observability_snapshot(self) -> dict:
        """The state the serving watchdog freezes into a capture
        artifact when an SLO anomaly fires: the engine's wall-time
        phase split, the scheduler's depth + drop counters, and the
        PageAllocator's occupancy — enough to tell 'engine got slow'
        from 'queue backed up' from 'out of pages'."""
        es = self.stats()
        return {
            "phase_split": {
                "step_time_s": round(es["step_time_s"], 4),
                "host_time_s": round(es["host_time_s"], 4),
                "table_ships": es["table_ships"],
            },
            "scheduler": {
                "queue_depth": self.scheduler.queue_depth(),
                "admitted": self.scheduler.admitted,
                "completed": self.scheduler.completed,
                "shed": self.scheduler.shed,
                "rejected": self.scheduler.rejected,
                "timed_out": self.scheduler.timed_out,
                "poisoned": self.scheduler.poisoned,
            },
            "allocator": {
                "free_pages": self.alloc.free_pages,
                "reserved_pages": self.alloc.reserved_pages,
                "n_pages": self.geom.n_pages,
                "pages_per_slot": [
                    self.alloc.slot_pages(i) for i in range(self.n_slots)
                ],
            },
            "active_slots": es["active_slots"],
            "tokens_per_s": round(es["tokens_per_s"], 2),
            "spec_accept_rate": round(es["spec_accept_rate"], 4),
            # trie stats ride along so a watchdog capture can tell
            # "out of pages" from "dedup regressed" (hot prefixes
            # falling out of the index under churn)
            # disaggregation: which role this replica plays and how many
            # requests are parked mid-handoff (phase "handoff" = prefill
            # finished, pages still streaming to the decode replica)
            "handoff": {
                "role": self.role,
                "handoffs_in": es["handoffs_in"],
                "handoffs_out": es["handoffs_out"],
                "handoff_bytes": es["handoff_bytes"],
                "pending": sum(
                    1 for s in self.slots
                    if s is not None and s.phase == "handoff"
                ),
                "affinity_bounced": es["affinity_bounced"],
            },
            "prefix": {
                "sharing": self.prefix_sharing,
                "hit_rate": round(es["prefix_hit_rate"], 4),
                "trie_pages": es["trie_pages"],
                "trie": (
                    self.trie.stats() if self.trie is not None else {}
                ),
                "dedup_ratio": round(es["dedup_ratio"], 4),
                "prefill_tokens_saved": es["prefill_tokens_saved"],
                "cow_pages": es["cow_pages"],
            },
        }

    # ---- device-side inputs ----------------------------------------------

    def _device_tables(self):
        """The block tables as a device array, re-shipped only when the
        allocator mutated since the last ship (the dirty flag) — a
        steady-state decode step reuses the cached copy instead of
        paying a host→device transfer per step."""
        if self.alloc.consume_dirty() or self._tables_dev is None:
            self._tables_dev = jnp.asarray(self.alloc.block_tables())
            self._table_ships += 1
        return self._tables_dev

    def _pages_bucket(self) -> int:
        """STATIC page-walk width for the jitted steps: the next power
        of two ≥ the max pages any slot holds, floored at 4 so tiny
        geometries don't churn compiles. Bounds the attention walk (and
        the gather reference's width) by what is actually resident
        while keeping recompiles to a handful over a slot's lifetime."""
        held = max(
            (self.alloc.slot_pages(i) for i in range(self.n_slots)),
            default=1,
        )
        b = 4
        while b < held:
            b *= 2
        if not self.page_bucketing:  # ablation: legacy full-pool width
            return self.geom.max_pages_per_slot
        return min(b, self.geom.max_pages_per_slot)

    # ---- the step loop ---------------------------------------------------

    def step(self) -> bool:
        """One engine iteration; returns False when fully idle (the
        server thread uses that to sleep instead of spinning)."""
        worked = self._finish_and_evict()
        worked = self._admit() or worked
        if self._t0 is None and any(self.slots):
            self._t0 = time.monotonic()
        if self.role == "prefill":
            # prefill-only replica: EVERY prefill slot advances one chunk
            # per step (large effective chunk) and the decode/spec batch
            # is never traced — finished prompts leave via handoff_sink
            return self._prefill_all() or worked
        worked = self._prefill_one() or worked
        if self.spec_k:
            worked = self._spec_batch() or worked
        else:
            worked = self._decode_batch() or worked
        return worked

    def drain(self, timeout: float = 120.0) -> None:
        """Step until queue and slots are empty (tests)."""
        deadline = time.monotonic() + timeout
        while self.scheduler.queue_depth() or self.active_slots():
            self.step()
            if time.monotonic() > deadline:
                raise TimeoutError("engine did not drain in time")

    @staticmethod
    def _slot_done(s: _Slot) -> bool:
        req = s.req
        return len(s.generated) >= req.max_new_tokens or (
            req.eos_id is not None
            and bool(s.generated)
            and s.generated[-1] == req.eos_id
        )

    def _finish_and_evict(self) -> bool:
        worked = False
        for i, s in enumerate(self.slots):
            if s is None or s.phase != "decode":
                continue
            if not self._slot_done(s):
                continue
            req = s.req
            if s.span is not None:
                s.span.end(tokens=len(s.generated), reason="completed")
                s.span = None
            self.scheduler.complete(
                req, [int(t) for t in s.prompt] + s.generated
            )
            self.alloc.evict(i)
            self.slots[i] = None
            worked = True
        return worked

    def _prefix_plan(self, req) -> Optional["prefix_mod.AdmissionPlan"]:
        """The admission recipe for ``req`` under prefix sharing: which
        committed pages its prompt can map, where prefill resumes. None
        when sharing is off or the trie has no usable match."""
        if self.trie is None:
            return None
        match = self.trie.lookup(req.prompt)
        if not match.pages and not match.tail_tokens:
            return None
        return prefix_mod.plan_admission(
            match, len(req.prompt), self.geom.page_size, self.prefill_chunk
        )

    def _footprint_tokens(self, req) -> int:
        """Tokens of page footprint an admission reserves. A
        prefill-role engine holds PROMPT-ONLY pages — generated tokens'
        K/V rows are written on the decode replica, so reserving them
        here would halve the prefill pool's concurrency for nothing.
        (The sampled first token is drawn from logits, never written.)"""
        if self.role == "prefill":
            return len(req.prompt)
        return req.total_tokens

    def _admit(self) -> bool:
        worked = False
        if self.draining:
            return worked
        while True:
            try:
                idx = self.slots.index(None)
            except ValueError:
                return worked

            def can(req):
                # oversize requests pass so they can be popped and FAILED
                # (they would block the head of the line forever)
                if req.total_tokens > self.geom.max_len:
                    return True
                # hit-aware footprint: read-only shared prefix pages are
                # mapped, not drawn from the free list — a hot-prefix
                # request can fit where a cold one of the same length
                # cannot (COW pages are fresh and get no discount)
                plan = self._prefix_plan(req)
                if self.role == "decode" and not prefix_mod.affinity_ok(
                    plan, len(req.prompt), self.affinity_suffix_max
                ):
                    return True  # popped to BOUNCE — takes no pages
                n_shared = len(plan.shared) if plan else 0
                return self.alloc.can_admit(
                    self._footprint_tokens(req), n_shared
                )

            req = self.scheduler.pop_next(
                can, lookahead=self.admission_lookahead
            )
            if req is None:
                return worked
            if req.total_tokens > self.geom.max_len:
                self.scheduler.count_rejected()
                self.scheduler.fail(req, AdmissionError(
                    f"request {req.rid} needs {req.total_tokens} tokens "
                    f"> slot capacity {self.geom.max_len}"
                ))
                continue
            # validate sampling params HERE so a poisoned request fails
            # its own future instead of raising in the step-loop thread
            try:
                req.sampling.validate()
                key_data = np.asarray(
                    jax.random.key_data(
                        jax.random.key(int(req.sampling.seed))
                    )
                )
            except Exception as exc:  # noqa: BLE001 — poisoned objects
                err = exc if isinstance(exc, AdmissionError) else (
                    AdmissionError(
                        f"request {req.rid} has invalid sampling "
                        f"params: {exc}"
                    )
                )
                self.scheduler.count_poisoned()
                self.scheduler.fail(req, err)
                continue
            # reserve the FULL prompt+generation footprint up front so a
            # decoding slot can never deadlock waiting for pages (a
            # prefill-role engine reserves prompt-only: the generation
            # pages live on the decode replica); on a prefix hit the
            # matched prefix maps existing pages instead of drawing
            # fresh ones, and prefill resumes at the plan's
            # chunk-aligned resume point
            plan = self._prefix_plan(req)
            if self.role == "decode" and not prefix_mod.affinity_ok(
                plan, len(req.prompt), self.affinity_suffix_max
            ):
                # the plan the router saw degraded (donor pages churned
                # out of the trie): bounce for re-dispatch through the
                # prefill pool rather than chunk-prefilling a cold
                # prompt here
                self._affinity_bounced += 1
                self.bounced.append(req)
                worked = True
                continue
            resume = 0
            if plan is not None:
                self.alloc.admit_shared(
                    idx, self._footprint_tokens(req), plan.prefix_pages
                )
                for logical, _src in plan.cow:
                    pair = self.alloc.cow_page(idx, logical)
                    if pair is not None:
                        self._copy_page(*pair)
                        self._cow_pages += 1
                resume = plan.resume
                self._prefix_hits += 1
                self._prefill_tokens_saved += resume
            else:
                self.alloc.admit(idx, self._footprint_tokens(req))
                if self.prefix_sharing:
                    self._prefix_misses += 1
            self._peak_dedup = max(self._peak_dedup, self.dedup_ratio())
            self.slots[idx] = _Slot(
                req=req, phase="prefill",
                prompt=np.asarray(req.prompt, np.int32),
                key_data=key_data,
                n_prefilled=resume,
                interned_pages=len(plan.shared) if plan else 0,
            )
            self.scheduler.record_admitted(req)
            tr = get_tracer()
            if tr.enabled:
                tr.instant(
                    "serving.admit", rid=req.rid,
                    replica=self.scheduler.replica, slot=idx,
                    re_admits=req.re_admits, prefix_resume=resume,
                )
            worked = True

    # ---- prefix sharing helpers ------------------------------------------

    def _copy_page(self, src: int, dst: int) -> None:
        """Copy one physical page's payload across every pool array —
        the device half of a COW duplication (all layers, one page)."""
        for k, v in self.pools.items():
            self.pools[k] = v.at[:, dst].set(v[:, src])

    def _intern_full_pages(self, i: int, s: _Slot) -> None:
        """Index the slot's newly COMMITTED full prompt pages. Only
        pages that are pure prompt — ``(j+1)*page_size <= len(prompt)``
        — and fully prefilled are eligible: a page carrying generated
        tokens (or an uncommitted tail) is not a reusable prefix."""
        if self.trie is None:
            return
        ps = self.geom.page_size
        full = min(int(s.n_prefilled), len(s.prompt)) // ps
        if full <= s.interned_pages:
            return
        row = self.alloc.block_tables()[i]
        self.trie.intern(s.prompt, full, row)
        s.interned_pages = full

    # ---- live KV-page migration (serving/migration.py) -------------------

    def export_pages(
        self, i: int, start: int = 0, stop: Optional[int] = None
    ) -> Dict[str, np.ndarray]:
        """Host copies of the physical pages slot ``i`` holds, in
        LOGICAL order — the donor half of a live migration. Pages ship
        exactly as stored (int8 payloads + per-block f32 scales, or
        bf16 rows), so the survivor's continuation attends to
        bitwise-identical cache state. ``start``/``stop`` slice the
        logical page range (a streaming handoff ships only the pages
        the last chunk committed). Read-only: the slot keeps its pages
        until :meth:`release_slot`, so a torn transfer can
        re-snapshot."""
        n = self.alloc.slot_pages(i)
        if stop is None:
            stop = n
        if not 0 <= start <= stop <= n:
            raise ValueError(
                f"page range [{start}, {stop}) outside the {n} pages "
                f"slot {i} holds"
            )
        phys = [int(p) for p in self.alloc.block_tables()[i, start:stop]]
        return {k: np.asarray(v[:, phys]) for k, v in self.pools.items()}

    def stage_pages(
        self, tag: str, page_start: int, pages: Dict[str, np.ndarray]
    ) -> None:
        """Scatter streamed handoff payloads into the physical pages of
        migration reservation ``tag`` BEFORE it commits — the decode
        side of a streaming handoff warms its reservation fragment by
        fragment, so ``import_slot(..., pages=None)`` at the end only
        rebuilds host state. Reserved pages are off the free list and
        in no block table, so no jitted step can read them; writes are
        idempotent per logical range (a restarted stream re-stages the
        same payloads into the same cells). Call under
        ``server.paused()`` — pool arrays are swapped."""
        phys = self.alloc.reservation(tag)
        if not phys:
            raise KeyError(f"no migration reservation {tag!r}")
        if set(pages) != set(self.pools):
            raise ValueError(
                f"staged pages carry pools {sorted(pages)}; this engine "
                f"stores {sorted(self.pools)} (mode={self.geom.mode})"
            )
        n = next(iter(pages.values())).shape[1]
        if n == 0:
            return
        if page_start + n > len(phys):
            raise ValueError(
                f"fragment pages [{page_start}, {page_start + n}) exceed "
                f"the {len(phys)}-page reservation {tag!r}"
            )
        tgt = jnp.asarray(phys[page_start:page_start + n], jnp.int32)
        for k, v in self.pools.items():
            self.pools[k] = v.at[:, tgt].set(jnp.asarray(pages[k], v.dtype))

    def note_handoff_bytes(self, n: int) -> None:
        """Account wire bytes a handoff shipped from/into this engine
        (the coordinator encodes off-thread, so the engine cannot see
        the blob sizes itself)."""
        self._handoff_bytes += int(n)

    def release_slot(self, i: int, *, reason: str = "migrated_out") -> None:
        """Drop a slot whose request moved out: free its pages without
        resolving the request's future (whoever owns the request now
        finishes it). ``reason`` is ``"migrated_out"`` (failover
        migration), ``"handoff_out"`` (committed prefill→decode
        handoff) or ``"handoff_abort"`` (degraded handoff — the request
        re-prefills elsewhere, so neither success counter moves)."""
        s = self.slots[i]
        if s is None:
            return
        if s.span is not None:
            s.span.end(tokens=len(s.generated), reason=reason)
            s.span = None
        self.alloc.evict(i)
        self.slots[i] = None
        if reason == "handoff_out":
            self._handoffs_out += 1
        elif reason == "migrated_out":
            self._migrated_out += 1

    def import_slot(
        self,
        req: Request,
        pages: Optional[Dict[str, np.ndarray]],
        *,
        phase: str,
        n_prefilled: int,
        generated: Sequence[int],
        reserved_tag: Optional[str] = None,
        handoff: bool = False,
    ) -> int:
        """Adopt a migrated (or handed-off) request mid-stream into a
        free slot.

        Commits the pages reserved under ``reserved_tag`` (or admits a
        fresh footprint when None), scatters the donated page payloads
        verbatim into those physical pages, and rebuilds the lane
        exactly where the donor stopped — same absolute positions, same
        generated prefix, sampling key re-derived from the request's
        seed. Because every sampling draw folds in the absolute buffer
        position, the continuation emits the never-evicted stream.

        ``pages=None`` (requires ``reserved_tag``) commits a reservation
        whose payloads were already streamed in via :meth:`stage_pages`
        — the final fragment of a streaming handoff only flips host
        state, no device scatter.

        Raises ``AdmissionError`` (with a retry-after hint) when no lane
        is free, and ``ValueError`` on a footprint/geometry mismatch —
        both leave the caller on the re-prefill fallback ladder.
        """
        if pages is None and reserved_tag is None:
            raise ValueError(
                "import_slot(pages=None) needs a reserved_tag whose pages "
                "were staged via stage_pages"
            )
        try:
            idx = self.slots.index(None)
        except ValueError:
            raise AdmissionError(
                f"no free slot for migrated request {req.rid}",
                retry_after_s=self.scheduler.retry_after_hint(),
            ) from None
        if pages is not None and set(pages) != set(self.pools):
            raise ValueError(
                f"migrated pages carry pools {sorted(pages)}; this engine "
                f"stores {sorted(self.pools)} (mode={self.geom.mode})"
            )
        if reserved_tag is not None:
            phys = self.alloc.commit_migration(reserved_tag, idx)
        else:
            if not self.alloc.can_admit(req.total_tokens):
                raise AdmissionError(
                    f"no pages for migrated request {req.rid}",
                    retry_after_s=self.scheduler.retry_after_hint(),
                )
            self.alloc.admit(idx, req.total_tokens)
            n = self.alloc.slot_pages(idx)
            phys = [int(p) for p in self.alloc.block_tables()[idx, :n]]
        if pages is not None:
            n_held = next(iter(pages.values())).shape[1]
            if n_held > len(phys):
                self.alloc.evict(idx)
                raise ValueError(
                    f"migrated request {req.rid} holds {n_held} pages but "
                    f"the reservation covers {len(phys)} — geometry mismatch"
                )
            tgt = jnp.asarray(phys[:n_held], jnp.int32)
            for k, v in self.pools.items():
                self.pools[k] = v.at[:, tgt].set(jnp.asarray(pages[k], v.dtype))
        key_data = np.asarray(
            jax.random.key_data(jax.random.key(int(req.sampling.seed)))
        )
        slot = _Slot(
            req=req,
            phase=phase,
            prompt=np.asarray(req.prompt, np.int32),
            key_data=key_data,
            n_prefilled=int(n_prefilled),
            generated=[int(t) for t in generated],
        )
        tr = get_tracer()
        if tr.enabled and phase == "decode":
            slot.span = tr.begin(
                "serving.decode", rid=req.rid,
                replica=self.scheduler.replica, slot=idx, resumed=True,
            )
        self.slots[idx] = slot
        # re-intern the imported prompt pages: the survivor's trie has
        # never seen them (sharing structure does not travel the wire —
        # the donor ships private payload copies), so future hot-prefix
        # requests on this replica can share them
        self._intern_full_pages(idx, slot)
        if self._t0 is None:
            self._t0 = time.monotonic()
        if handoff:
            self._handoffs_in += 1
        else:
            self._migrated_in += 1
        return idx

    def _sampling_arrays(self, lanes):
        """Per-lane sampling inputs for the jitted steps: threefry key
        data, temperature, top_k, top_p. Idle lanes carry defaults
        (greedy, zero key) so their — masked — draws are well-defined."""
        n = len(lanes)
        keys = np.zeros((n, 2), np.uint32)
        temp = np.zeros(n, np.float32)
        top_k = np.zeros(n, np.int32)
        top_p = np.ones(n, np.float32)
        for j, i in enumerate(lanes):
            s = self.slots[i]
            if s is None:
                continue
            keys[j] = s.key_data
            sp = s.req.sampling
            temp[j] = sp.temperature
            top_k[j] = sp.top_k
            top_p[j] = sp.top_p
        return (
            jnp.asarray(keys), jnp.asarray(temp),
            jnp.asarray(top_k), jnp.asarray(top_p),
        )

    def _prefill_one(self) -> bool:
        for i, s in enumerate(self.slots):
            if s is None or s.phase != "prefill":
                continue
            self._prefill_slot(i, s)
            return True
        return False

    def _prefill_all(self) -> bool:
        """Prefill-role stepping: every prefill slot advances one chunk
        this step — with no decode batch to interleave with, there is
        nothing to yield to."""
        todo = [
            (i, s) for i, s in enumerate(self.slots)
            if s is not None and s.phase == "prefill"
        ]
        for i, s in todo:
            self._prefill_slot(i, s)
        return bool(todo)

    def _prefill_slot(self, i: int, s: _Slot) -> None:
        """Advance one slot by one prefill chunk (all roles share this
        body; the roles differ only in where a finished prompt goes)."""
        p = len(s.prompt)
        clen = min(self.prefill_chunk, p - s.n_prefilled)
        chunk = np.zeros(self.prefill_chunk, np.int32)
        chunk[:clen] = s.prompt[s.n_prefilled:s.n_prefilled + clen]
        tables = self._device_tables()[i:i + 1]
        tr = get_tracer()
        sp = None
        if tr.enabled:
            sp = tr.begin(
                "serving.prefill_chunk", rid=s.req.rid,
                replica=self.scheduler.replica, slot=i,
                start=s.n_prefilled, tokens=clen,
            )
        t0 = time.monotonic()
        tok0, self.pools = self._chunk_fn(
            self.params, self.pools, tables,
            jnp.asarray(chunk[None]),
            jnp.asarray([s.n_prefilled], jnp.int32),
            jnp.asarray([clen], jnp.int32),
            *self._sampling_arrays([i]),
            self._pages_bucket(),
        )
        tok0 = np.asarray(tok0)
        self._step_time += time.monotonic() - t0
        if sp is not None:
            sp.end()
        s.n_prefilled += clen
        self._prefill_tokens += clen
        self._prefill_chunks += 1
        self._intern_full_pages(i, s)
        if s.n_prefilled < p:
            if self.role == "prefill" and self.handoff_sink is not None:
                # streaming handoff: the chunk just committed may have
                # filled whole pages — ship them now, overlapped with
                # the next chunk's compute
                self.handoff_sink(i, s, "chunk")
            return
        s.generated = [int(tok0[0])]
        self.scheduler.record_first_token(s.req)
        self._tokens += 1
        if self.role == "prefill":
            if self._slot_done(s):
                # finished at its first token (max_new=1, or EOS drawn):
                # nothing to decode downstream — complete locally and
                # cancel any fragments already streamed
                self.scheduler.complete(
                    s.req, [int(t) for t in s.prompt] + s.generated
                )
                self.alloc.evict(i)
                self.slots[i] = None
                if self.handoff_sink is not None:
                    self.handoff_sink(i, s, "local_done")
                return
            if self.handoff_sink is None:
                raise RuntimeError(
                    f"prefill-role engine finished {s.req.rid} with no "
                    "handoff sink attached — wire a HandoffCoordinator "
                    "(serving/disagg.py) or run role='unified'"
                )
            # park until the decode replica commits; the coordinator
            # releases the slot (release_slot) after the handoff lands
            s.phase = "handoff"
            self.handoff_sink(i, s, "done")
            return
        s.phase = "decode"
        if tr.enabled:
            # the long occupancy span: first token → finish or
            # migrate-out; the survivor re-opens it resumed=True
            s.span = tr.begin(
                "serving.decode", rid=s.req.rid,
                replica=self.scheduler.replica, slot=i,
            )

    def _decode_batch(self) -> bool:
        # a slot can complete within the step that finishes its prefill
        # (max_new=1, or EOS on the prefill token): it must not decode
        # an extra token before the next _finish_and_evict sees it
        live = [
            i for i, s in enumerate(self.slots)
            if s is not None and s.phase == "decode"
            and not self._slot_done(s)
        ]
        if not live:
            return False
        tokens = np.zeros(self.n_slots, np.int32)
        pos = np.zeros(self.n_slots, np.int32)
        valid = np.zeros(self.n_slots, bool)
        for i in live:
            s = self.slots[i]
            tokens[i] = s.generated[-1]
            pos[i] = len(s.prompt) + len(s.generated) - 1
            valid[i] = True
        t0 = time.monotonic()
        tok, self.pools = self._decode_fn(
            self.params, self.pools, self._device_tables(),
            jnp.asarray(tokens), jnp.asarray(pos), jnp.asarray(valid),
            *self._sampling_arrays(range(self.n_slots)),
            self._pages_bucket(),
        )
        tok = np.asarray(tok)
        self._step_time += time.monotonic() - t0
        for i in live:
            self.slots[i].generated.append(int(tok[i]))
            self._tokens += 1
        return True

    def _spec_batch(self) -> bool:
        """Speculative variant of ``_decode_batch``: every decoding slot
        contributes a verify chunk ``[last token, drafts..., pad]`` and
        the jitted verify step commits 1..spec_k+1 tokens per slot.
        Falls back to plain decode on steps where NO slot has a draft
        (the verify chunk would just be a wider decode)."""
        live = [
            i for i, s in enumerate(self.slots)
            if s is not None and s.phase == "decode"
            and not self._slot_done(s)
        ]
        if not live:
            return False
        c = self.spec_k + 1
        tokens = np.zeros((self.n_slots, c), np.int32)
        start = np.zeros(self.n_slots, np.int32)
        valid = np.zeros(self.n_slots, bool)
        n_draft = np.zeros(self.n_slots, np.int32)
        for i in live:
            s = self.slots[i]
            # never draft past the request's budget: the LAST emitted
            # token must be the one that hits max_new_tokens, so drafts
            # beyond remaining-1 could commit K/V rows the allocator
            # never reserved. k_eff keeps every commit inside the
            # admission footprint.
            remaining = s.req.max_new_tokens - len(s.generated)
            k_eff = max(0, min(self.spec_k, remaining - 1))
            drafts = list(
                self.draft.propose(
                    list(s.prompt) + s.generated, k_eff
                )
            )[:k_eff]
            tokens[i, 0] = s.generated[-1]
            tokens[i, 1:1 + len(drafts)] = drafts
            start[i] = len(s.prompt) + len(s.generated) - 1
            valid[i] = True
            n_draft[i] = len(drafts)
        if not n_draft.any():
            return self._decode_batch()
        tr = get_tracer()
        sp = None
        if tr.enabled:
            sp = tr.begin(
                "serving.spec_verify", replica=self.scheduler.replica,
                n_live=len(live), drafts=int(n_draft.sum()),
                rids=",".join(self.slots[i].req.rid for i in live),
            )
        t0 = time.monotonic()
        tgt, n_emit, self.pools = self._verify_fn(
            self.params, self.pools, self._device_tables(),
            jnp.asarray(tokens), jnp.asarray(start), jnp.asarray(valid),
            jnp.asarray(n_draft),
            *self._sampling_arrays(range(self.n_slots)),
            self._pages_bucket(),
        )
        tgt = np.asarray(tgt)
        n_emit = np.asarray(n_emit)
        self._step_time += time.monotonic() - t0
        if sp is not None:
            sp.end(emitted=int(n_emit.sum()))
        for i in live:
            s = self.slots[i]
            n = int(n_emit[i])
            self._draft_tokens += int(n_draft[i])
            self._accepted_tokens += n - 1
            for j in range(n):
                s.generated.append(int(tgt[i, j]))
                self._tokens += 1
                if len(s.generated) >= s.req.max_new_tokens or (
                    s.req.eos_id is not None
                    and s.generated[-1] == s.req.eos_id
                ):
                    break
        return True
