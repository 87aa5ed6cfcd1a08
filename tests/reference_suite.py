"""One harness for the reference suites (``tests/test_*_reference.py``):
a model's architecture at a tiny size on the CPU against the benchmark's
plain reference. It holds what every such file used to spell out again,
and it is where the compiles are counted: a test second here is an XLA
compile, so a program is compiled once a module, not twice a case.

A new model's file SUPPLIES

- ``TINY`` (overrides of its preset: float32, ``remat="full"``, a few
  layers, the shallowest depth that has every layer kind; a second
  period only in the one test that is about stacking), ``SIZE_KEYS``
  (the configuration file's ``sizes``, read off the ``ModelConfig``),
  its plain module (``benchmarks/references/*_plain.py``) and its
  defect table (``benchmarks/tests/*_defects.py``'s ``PLANT`` and
  ``CAUGHT_BY``, and whatever a configuration can state, as a dict);
- ``SUITE = Suite("<preset>", plain, TINY, SIZE_KEYS, seq=.., q_block=..,
  make=<how its weights are seeded>)``, ``make`` built from ``seeded``
  below (norm scales off their initial values, a head that reads the
  token table, a prediction module that passes the next token through:
  whichever the model has);
- a module-scoped ``model`` fixture returning ``SUITE.model()`` (the
  config and ``make``'s weights, drawn in one program; another depth or
  seed: ``SUITE.model(seed, **overrides)``, ``SUITE.weights(cfg, seed)``),
  its test functions — which call the suite, so that a test's id is the
  file's own — and the tests of its own mechanisms. A model of two
  periods runs its defect and gradient cases on a second fixture of one
  (``tests/test_qwen3_next_reference.py``'s ``period``): a compile is
  linear in depth.

and GETS

- ``SUITE.compare(cfg, params)``: the cell's comparison
  (``benchmarks/lib/routed.py``), teacher-forced and free-running, as
  ``{check: (ok, value)}`` and the record. The SOUND program's logits,
  choices and scalar losses are computed once for a (config, weights)
  and handed to every test that asks (``SUITE.forward``,
  ``SUITE.losses``); so is the free-running reference's loss, and
  whatever else a file computes of the sound program (``SUITE.once``);
- ``SUITE.catches(monkeypatch, model, plant, caught_by)``: one defect
  planted (a dict of config overrides, or ``plant(patch, cfg)``), the
  program compiled AFRESH (a planted program is never served from, nor
  kept in, the memo) and only as far as the checks in ``caught_by``
  read: a defect that the logits checks catch compiles the forward and
  not ``program_losses``'s second copy of the trunk. The assertion is
  the files' own: a check of ``caught_by`` failed;
- ``SUITE.gradients_match(model, terms=..)``, ``SUITE.flops_terms``,
  ``SUITE.refuses`` (``REFUSALS``), ``SUITE.shares_add_up``: the
  comparisons that were the same code in every file.

``tests/test_qwen3_next_reference.py`` is the file to copy from."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import flops as flopslib
from benchmarks.lib import routed
from dlrover_tpu.models import decoder, generate, get_config
from dlrover_tpu.parallel import moe

LOGITS = ("logits_vs_reference", "logits_rms_vs_reference")
# the checks that read the program's logits and choices and none of its
# step metrics
READ_NO_LOSS = frozenset(
    LOGITS + ("choices_valid", "groups_valid", "group_regret",
              "routing_regret")
)
# ``program`` for a comparison whose loss checks nobody reads: they fail
# (NaN, "not among the program's step metrics") and are dropped
_UNREAD = {"loss": float("nan")}


def batch(seq, doubled=True):
    """Two rows of seeded tokens under 256 and their next ones.
    ``doubled``: every token twice in a row (a a b b c c ...), so that
    the next token is the present one half of the time, and the one
    after that never."""
    rng = np.random.default_rng(7)
    if doubled:
        half = rng.integers(0, 256, (2, seq // 2 + 1))
        data = np.repeat(half, 2, axis=1)[:, : seq + 1]
    else:
        data = rng.integers(0, 256, (2, seq + 1))
    data = jnp.asarray(data, jnp.int32)
    return {"tokens": data[:, :-1], "targets": data[:, 1:]}


def seeded(cfg, seed=0, scales=None, by_index=False, spread=0.3, head=True,
           module=False):
    """``decoder.init``'s seeded weights, made to tell more apart:

    - ``scales`` (a key, or None): every norm scale, norm offset and
      per-head scale moved off its initial value by ``spread`` x a
      normal draw. At 0 and 1 a program that reads ``w`` for ``1 + w``,
      norms after the gate or leaves a scale out could not be told from
      a sound one. The draws come leaf by leaf from the key's splits,
      or with ``by_index`` from the key with the leaf's index folded in
      (the two spellings the files had; the draws are the files' own);
    - ``head``: a head that reads the token table, a model whose
      predictions lean towards the token it was just given. With
      predictions that know nothing of the targets (seeded weights,
      uniform tokens) a loss asked for the wrong tokens reads the same
      as the right one, and a shifted target could not show;
    - ``module``: a prediction module's projection that passes the next
      token's embedding through, for the same reason."""
    params = decoder.init(jax.random.key(seed), cfg)
    if scales is not None:
        key, drawn = scales, iter(jax.random.split(scales, 64))
        leaves, treedef = jax.tree_util.tree_flatten_with_path(params)
        moved = []
        for i, (path, leaf) in enumerate(leaves):
            if path[-1].key == "scale":
                k = jax.random.fold_in(key, i) if by_index else next(drawn)
                leaf = leaf + spread * jax.random.normal(k, leaf.shape)
            moved.append(leaf)
        params = jax.tree_util.tree_unflatten(treedef, moved)
    d = cfg.d_model
    if head:
        params["lm_head"]["w"] = (
            params["embed"]["tokens"].T / (0.02 * d ** 0.5)
        )
    if module:
        params["mtp"]["eh_proj"] = jnp.concatenate(
            [jnp.eye(d), 0.25 * params["mtp"]["eh_proj"][d:]]
        )
    return params


# the paths that cannot run a train-only model say so, by its name
REFUSALS = {
    "init_kv_cache": lambda cfg, p, t: decoder.init_kv_cache(cfg, 2, 64),
    "prefill": lambda cfg, p, t: decoder.prefill(p, t, cfg, 64),
    "decode_step": lambda cfg, p, t: decoder.decode_step(
        p, t[:, 0], {}, 0, cfg
    ),
    "prefill_chunk": lambda cfg, p, t: decoder.prefill_chunk(
        p, t, {}, 0, cfg
    ),
    "decode_step_paged": lambda cfg, p, t: decoder.decode_step_paged(
        p, t[:, 0], {}, None, jnp.zeros(2, jnp.int32), None, cfg
    ),
    "verify_chunk": lambda cfg, p, t: decoder.verify_chunk(p, t, {}, 0, cfg),
    "sample": lambda cfg, p, t: generate.sample(
        p, cfg, t, 4, jax.random.key(0)
    ),
}


def grads_close(got, want, atol):
    """Every leaf of ``got`` against ``want``'s, by the leaf's largest
    entry; and no leaf of ``want`` is all zeros (a parameter the
    objective does not reach compares nothing)."""
    flat_got = jax.tree_util.tree_leaves_with_path(got)
    flat_want = jax.tree.leaves(want)
    assert len(flat_got) == len(flat_want)
    for (path, a), b in zip(flat_got, flat_want):
        name = jax.tree_util.keystr(path)
        scale = float(jnp.max(jnp.abs(b)))
        assert scale > 0, name
        err = float(jnp.max(jnp.abs(a - b))) / scale
        assert err <= atol, (name, err)


class Suite:
    """One model's configuration, reference and memo (module docstring)."""

    def __init__(self, name, plain, tiny, size_keys, *, seq, q_block, make,
                 tolerances=(1e-3, 1e-3, 1e-4), norm_eps=1e-6, doubled=True):
        self.name, self.plain, self.tiny = name, plain, tiny
        self.size_keys, self.norm_eps = tuple(size_keys), norm_eps
        self.seq, self.q_block, self.make = seq, q_block, make
        self.tolerances, self.doubled = tolerances, doubled
        self._memo = {}

    def cfg(self, **over):
        return get_config(self.name, **{**self.tiny, **over})

    def sizes(self, cfg):
        """The configuration file's ``sizes``, read off ``cfg``;
        ``norm_eps`` where the program fixes it in code."""
        sizes = {k: getattr(cfg, k) for k in self.size_keys}
        if self.norm_eps is not None:
            sizes["norm_eps"] = self.norm_eps
        return sizes

    def batch(self, seq=None):
        return batch(seq or self.seq, doubled=self.doubled)

    def weights(self, cfg, seed=0):
        """``make``'s weights, drawn in one program (eager, a draw is a
        dispatch a leaf and an operation)."""
        return jax.jit(lambda: self.make(cfg, seed))()

    def model(self, seed=0, **over):
        cfg = self.cfg(**over)
        return cfg, self.weights(cfg, seed)

    def once(self, what, cfg, params, made):
        """``made()``, once for ``what``, a config (by value:
        ``ModelConfig`` is frozen) and weights (by object, kept alive
        beside the result)."""
        key = (what, cfg, id(params))
        if key not in self._memo:
            self._memo[key] = (made(), params)
        return self._memo[key][0]

    def forward(self, cfg, params):
        """The SOUND program's (logits, choices) on the suite's batch."""
        return self.once(
            "forward", cfg, params,
            lambda: routed.program_logits_and_choices(
                params, self.batch()["tokens"], cfg
            ),
        )

    def losses(self, cfg, params):
        """The SOUND program's scalar step metrics, as floats."""
        return self.once(
            "losses", cfg, params,
            lambda: routed.program_losses(params, self.batch(), cfg),
        )

    def free_loss(self, params, sizes):
        """The free-running reference's mean loss: it never sees the
        program, so it is one number for the sound case and every
        defect."""

        def free():
            with jax.default_matmul_precision("highest"):
                return float(jax.jit(
                    lambda p, b: self.plain.loss_and_logits(
                        p, b, sizes, self.q_block
                    )[0]
                )(params, self.batch()))

        return self.once("free", tuple(sorted(sizes.items())), params, free)

    def compare(self, cfg, params, sizes=None, tolerances=None, planted=False,
                read=None, reference_params=None):
        """The cell's comparison, teacher-forced and free-running.

        ``planted``: the program carries a defect that the config may
        not show: compiled afresh, nothing kept. ``read``: the checks
        the caller reads (None: all); where none of them reads the
        program's losses, ``program_losses`` is not compiled and the
        checks that read it are left out. ``reference_params``: the
        reference's weights where the program's differ."""
        sizes = sizes or self.sizes(cfg)
        batch = self.batch()
        ref_params = params if reference_params is None else reference_params
        needs_losses = read is None or not set(read) <= READ_NO_LOSS
        if planted:
            logits, choices = routed.program_logits_and_choices(
                params, batch["tokens"], cfg
            )
            program = (
                routed.program_losses(params, batch, cfg)
                if needs_losses else None
            )
        else:
            logits, choices = self.forward(cfg, params)
            program = self.losses(cfg, params) if needs_losses else None
        results, record = routed.compare(
            self.plain, ref_params, batch, sizes, self.q_block, logits,
            choices, program or _UNREAD, tolerances or self.tolerances,
        )
        if program is None:
            results = [r for r in results if r[0] in READ_NO_LOSS]
        else:
            free = self.free_loss(ref_params, sizes)
            err = abs(program["loss"] - free) / free
            results.append(
                ("loss_vs_free_reference", err <= routed.FREE_LOSS_TOL, err,
                 routed.FREE_LOSS_TOL)
            )
        return {name: (ok, value) for name, ok, value, _ in results}, record

    def catches(self, monkeypatch, model, plant, caught_by,
                program_params=None):
        """One defect planted in the program; the reference keeps the
        sound sizes and weights. A check of ``caught_by`` has to fail."""
        cfg, params = model
        program_cfg = cfg
        if isinstance(plant, dict):
            program_cfg = dataclasses.replace(cfg, **plant)
        else:
            plant(monkeypatch.setattr, cfg)
        checks, _ = self.compare(
            program_cfg, params if program_params is None else program_params,
            sizes=self.sizes(cfg), planted=True, read=caught_by,
            reference_params=params,
        )
        failed = {name for name, (ok, _) in checks.items() if not ok}
        assert failed & set(caught_by), (plant, checks)
        return checks

    def gradients_match(self, model, terms=(), atol=2e-4, forced=True):
        """d(objective)/d(params) of the program against ``jax.grad`` of
        the plain reference: sent to the program's experts and with the
        reference's own ``terms`` of the objective added (``forced``),
        or free-running. Returns (got, want)."""
        cfg, params = model
        batch, sizes, plain = self.batch(), self.sizes(cfg), self.plain
        choices = self.forward(cfg, params)[1] if forced else None

        def objective(p):
            if not forced:
                return plain.loss_and_logits(p, batch, sizes, self.q_block)[0]
            ce, _, got = plain.loss_and_logits_routed(
                p, batch, sizes, self.q_block, choices
            )
            return ce + sum(got[term] for term in terms)

        got = jax.jit(
            jax.grad(lambda p: decoder.loss_fn(p, batch, cfg=cfg)[0])
        )(params)
        with jax.default_matmul_precision("highest"):
            want = jax.jit(jax.grad(objective))(params)
        grads_close(got, want, atol)
        return got, want

    def flops_terms(self, cfg, seq):
        """``cfg.flops_per_token`` is the reference's required terms;
        returns them."""
        terms = self.plain.required_terms(self.sizes(cfg), seq)
        assert cfg.flops_per_token(seq) == pytest.approx(
            flopslib.flops_of(terms), rel=1e-12
        )
        return terms

    def refuses(self, model, path, match):
        cfg, params = model
        with pytest.raises(ValueError, match=match):
            REFUSALS[path](cfg, params, self.batch()["tokens"])

    def shares_add_up(self, shares, held, cut=("w_up", "w_gate_proj", "w_down"),
                      each=False, **over):
        """``shares`` chips hold ``held`` experts each of one routed
        block. Their routed parts, and the shared expert ONCE where the
        model has one, add up to what the uncut reference gives for the
        whole block: nothing is lost or counted twice at the seams, and
        a token's weights are over all it chose. ``each``: every
        share's part is the reference's share too. Returns the block's
        config and weights."""
        whole = self.cfg(n_experts_held=0, **over)
        full = moe.init_moe_params(jax.random.key(3), whole, lead=())
        g = jax.random.normal(jax.random.key(4), (2, 32, whole.d_model))
        rows_in = g.reshape(64, -1)
        sizes = dict(
            self.sizes(whole), n_experts_held=shares * held, expert_offset=0
        )
        with jax.default_matmul_precision("highest"):
            want = self.plain._routed(rows_in, full, sizes, None)[0]
            total, rows = 0.0, 0.0
            if "shared" in full:
                total = moe._shared_expert(g, full["shared"], None)
            for rank in range(shares):
                cfg = dataclasses.replace(
                    whole, n_experts_held=held, expert_offset=rank * held
                )
                here = slice(rank * held, (rank + 1) * held)
                part = dict(full, **{k: full[k][here] for k in cut})
                out, aux = moe._moe_block_ragged(g, part, cfg)
                total = total + out
                rows += float(aux["moe_held_rows"])
                if each:
                    mine = self.plain._routed(
                        rows_in, part,
                        dict(sizes, n_experts_held=held,
                             expert_offset=rank * held),
                        None,
                    )[0]
                    np.testing.assert_allclose(
                        np.asarray(out).reshape(64, -1), np.asarray(mine),
                        rtol=2e-5, atol=2e-5,
                    )
        np.testing.assert_allclose(
            np.asarray(total).reshape(64, -1), np.asarray(want),
            rtol=2e-5, atol=2e-5,
        )
        # every (token, choice) row went to exactly one share
        assert rows == 2 * 32 * whole.expert_top_k
        return whole, full
