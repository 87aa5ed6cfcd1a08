"""What the gated delta rule HAS to do, and the readers' shared parts
for the cell that runs it (``gdn.*``; PR 63).

The count is of the RECURRENCE and not of any chunking, so it reads the
same work whatever implements it. For one token and value head, with
Dk key and Dv value channels (``S`` is Dk x Dv):

    S' = α S          Dk Dv multiplies
    S'ᵀ k             Dk Dv multiply-adds
    S' + β k (.)ᵀ     Dk Dv multiply-adds
    Sᵀ q              Dk Dv multiply-adds

7 Dk Dv operations forward; the backward of each is twice its forward
(a cotangent for each of two operands), so a training step REQUIRES
21 Dk Dv a token, value head and layer. Recomputation does not count.

Bytes, once a pass, as the program stores them: the forward reads q and
k (Hk heads of Dk), v (Hv of Dv), g and β (Hv, float32) and writes o
(Hv of Dv); the backward reads all of those and o's cotangent and
writes the five cotangents. q, k, v and o are float32 in the program
(``decoder._gdn_block`` keeps float32 between the mixer's matmuls), so
that is what is counted: a body that took them in bf16 would move half
and be held to half. The state never leaves the
chip's fast memory in the count: a body that writes it out (the XLA
body's chunk states) does more than this and reads a lower share.

``roofline`` holds the self seconds under the scope ``gdn.rule`` to the
LONGER of operations over ``peaks.bf16_flops`` and bytes over
``peaks.hbm_bytes_s``: no implementation can pass 100% of it.
"""

from benchmarks.lib.trace import scope_seconds


def rule_operations(sizes, tokens):
    """Operations a training step requires of the rule over ``tokens``
    tokens, every linear layer: 21 a token, value head and state cell."""
    cells = sizes["gdn_key_dim"] * sizes["gdn_value_dim"]
    return 21.0 * cells * sizes["gdn_value_heads"] * tokens * layers(sizes)


def rule_bytes(sizes, tokens):
    """Bytes a training step's two passes over the rule move at the
    least, every linear layer: q, k, v, o, g and β float32, 4 bytes."""
    keys = 2 * sizes["gdn_key_heads"] * sizes["gdn_key_dim"] * 4
    values = sizes["gdn_value_heads"] * sizes["gdn_value_dim"] * 4
    gates = 2 * sizes["gdn_value_heads"] * 4
    operands = keys + values + gates
    forward = operands + values
    backward = operands + values + operands
    return float(forward + backward) * tokens * layers(sizes)


def layers(sizes):
    """The linear (gated-delta-rule) layers of the configuration."""
    return sizes["layer_pattern"].count("G")


def first_device(run):
    """The first device's entry of a traced run, or None."""
    trace = run["trace"]
    if not trace or not trace["per_device"]:
        return None
    return trace["per_device"][0]


def scope_rows(run, metric, scopes):
    """(self seconds under any of ``scopes``, the first device's busy
    seconds) of a traced run, a row counted once; the rows found under
    each scope go on a ``BENCH`` line (``event: scope_rows``). None
    without a device trace, and None — nothing raised — where the
    program has no such scope (a parent that lacks what PR 63 added):
    the metric is then left out of the line, which the driver sees."""
    first = first_device(run)
    if first is None:
        return None
    rows = {scope: scope_seconds(first, (scope,)) for scope in scopes}
    run["say"](
        event="scope_rows", metric=metric, busy_s=first["busy_s"],
        modules=first.get("modules"),
        rows={k: [len(v), sum(v.values())] for k, v in rows.items()},
    )
    if not all(rows.values()) or not first["busy_s"]:
        return None
    return sum(scope_seconds(first, scopes).values()), first["busy_s"]


def share(run, metric, scopes):
    """Percent of the first device's busy time under ``scopes``."""
    got = scope_rows(run, metric, scopes)
    return None if got is None else 100.0 * got[0] / got[1]


def traced_steps(spans):
    """Dispatches inside the last traced window
    (``zero.wire_gb_per_s``'s count)."""
    windows = [(s, e) for n, s, e in spans.spans if n == "traced_window"]
    if not windows:
        return 0
    lo, hi = windows[-1]
    return sum(
        1 for n, s, e in spans.spans if n == "dispatch" and lo <= s and e <= hi
    )


def roofline(run, metric="gdn.rule_roofline"):
    """Percent of its roofline the rule reaches in the traced steps."""
    got = scope_rows(run, metric, ("gdn.rule",))
    if got is None:
        return None
    steps = traced_steps(run["spans"])
    if not steps:
        return None
    sizes, tokens = run["sizes"], run["window"]["tokens"] * steps
    floor = max(
        rule_operations(sizes, tokens) / run["peaks"].bf16_flops,
        rule_bytes(sizes, tokens) / run["peaks"].hbm_bytes_s,
    )
    return 100.0 * floor / got[0]
