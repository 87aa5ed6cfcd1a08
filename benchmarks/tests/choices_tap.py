"""The program's expert choices, for the tests, from a program that
does not hand them over yet.

``lib/routed.py`` takes the choices from the program's own
``decoder.forward(..., return_aux=True)[1]["moe_choices"]``. A
``benchmark`` PR may not edit the program, so until a PR that may do
brings that key, the tests tap the router instead: ``moe._route`` is
wrapped while the forward is traced and its expert ids leave each layer
of the scan through an ordered host callback. Same forward, same
compiled arithmetic, one side effect more. Not part of the benchmark:
``run.py`` refuses a routed configuration on a program without the key.
"""

import numpy as np

from benchmarks.lib import routed
from benchmarks.lib.device import Refused

_program_logits_and_choices = routed.program_logits_and_choices


def logits_and_choices(params, tokens, cfg, sizes=None):
    """``routed.program_logits_and_choices``; the tap where the program
    refuses."""
    try:
        return _program_logits_and_choices(params, tokens, cfg, sizes)
    except Refused:
        return tapped_logits_and_choices(params, tokens, cfg)


def tapped_logits_and_choices(params, tokens, cfg, sizes=None):
    import jax
    import jax.numpy as jnp

    from dlrover_tpu.models import decoder
    from dlrover_tpu.parallel import moe

    seen = []  # one [B, S, k] per layer, in layer order
    route = moe._route

    def tapped(x, moe_params, cfg, rng):
        out = route(x, moe_params, cfg, rng)
        jax.debug.callback(
            lambda ids: seen.append(np.asarray(ids)), out[3], ordered=True
        )
        return out

    moe._route = tapped
    try:
        logits = jax.jit(lambda p, t: decoder.forward(p, t, cfg))(params, tokens)
        jax.block_until_ready(logits)
        jax.effects_barrier()
    finally:
        moe._route = route
    return logits, jnp.asarray(np.stack(seen), jnp.int32)
