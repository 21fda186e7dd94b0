"""The router's stages in a traced run of a cluster cell.

The program wraps the router's own work in named scopes besides the five
stages of ``access_group`` (``bench/stages.py``): ``ditto.route``
(packing each source's requests per destination, unpacking them at the
shard, merging the answers back onto their lanes), ``ditto.exchange``
(the ``all_to_all`` collectives) and ``ditto.sync`` (the cross-shard
weight sync).  Each runner operation's scope is looked up in the cluster
runner's compiled HLO text, which the program gives through
``repro.core.execute.cluster_runner_hlo``.  As in ``bench/stages.py``
the names live here: a renamed scope cannot change in silence what a
metric measures.

A program without a cluster runner's text reads None.  A program that
gives one is held to every scope: a traced window whose runner
operations lack any of the three router scopes, or all five stages, is
an error, as is a runner operation the text does not hold.
"""

from __future__ import annotations

import bisect

from bench import stages
from bench.tracing import merge

ROUTER = ("route", "exchange", "sync")
GROUPS = ROUTER + ("shard_access",)


def op_groups(hlo_text: str) -> dict:
    """``{instruction: (type, opcode, group)}`` of a compiled HLO module's
    text; ``group`` is one of ``GROUPS`` by the first ``ditto.*`` scope of
    the instruction's ``op_name`` (any ``access_group`` stage counts as
    ``shard_access``), or None."""
    scopes = {stages.SCOPE + g: g for g in ROUTER}
    scopes.update({stages.SCOPE + st: "shard_access"
                   for st in stages.STAGES})
    out = {}
    for line in hlo_text.splitlines():
        ins = stages._instr(line)
        if not ins:
            continue
        name = stages._OP_NAME.search(line)
        group = next((scopes[c] for c in name.group(1).split("/")
                      if c in scopes), None) if name else None
        out[ins[0]] = (*ins[1:], group)
    return out


def group_ns(s, names, table: dict) -> dict:
    """Device time in the traced window of the runner modules (names
    starting with one of ``names``) on the chip where they take most,
    per group: ``{group: ns}``, each the union of its operations'
    intervals inside the runner's, and ``"runner"`` the runner's own."""
    t0, t1 = s.window()
    names = tuple(names)
    dev = max(s.devices, key=lambda d: s.module_ns(
        d, lambda n: n.startswith(names), t0, t1))
    mods = merge([m for m in dev.modules if m[0].startswith(names)], t0, t1)
    starts = [a for a, _ in mods]
    by_group = {g: [] for g in GROUPS}
    for op in dev.ops:
        i = bisect.bisect_right(starts, op[1]) - 1
        if i < 0 or op[1] >= mods[i][1]:
            continue  # not inside a runner module
        ins = stages._instr(op[0])
        got = table.get(ins[0]) if ins else None
        if got is None or got[:2] != ins[1:]:
            raise LookupError(
                f"runner operation {op[0][:120]!r} is not in the cluster "
                "runner's HLO as the program gives it: the HLO is of "
                "another program")
        if got[2]:
            by_group[got[2]].append(op)
    runner = sum(b - a for a, b in mods)
    missing = [g for g in GROUPS if not by_group[g]]
    if runner and missing:
        raise LookupError(
            "no runner operation carries the scope of "
            + ", ".join(missing) + " in the traced window: the program's "
            "scopes have other names")
    out = {g: sum(e - b for a, z in mods for b, e in merge(ops, a, z))
           for g, ops in by_group.items()}
    out["runner"] = runner
    return out


_LAST = [None, None]  # (Summary, its split), one run at a time


def round_us(ctx):
    """``group_ns`` of a traced run per traced round, in us; None where the
    run holds nothing to read, its configuration is not a cluster's (no
    ``n_shards``) or the program gives no cluster runner."""
    s = ctx.traced
    if (s is None or not s.devices or not ctx.traced_rounds
            or "n_shards" not in ctx.config):
        return None
    if _LAST[0] is not s:
        _LAST[:] = [s, _split(ctx)]
    split = _LAST[1]
    if split is None:
        return None
    return {k: v / 1e3 / ctx.traced_rounds for k, v in split.items()}


def _split(ctx):
    s = ctx.traced
    if not s.runner_ns(ctx.config["runner_modules"]):
        return None
    text = runner_text(ctx)
    if text is None:
        return None
    return group_ns(s, ctx.config["runner_modules"], op_groups(text))


def runner_text(ctx):
    """The compiled HLO text of the cluster runner the run's calls ran,
    from the program; None for a program that gives none."""
    try:
        from repro.core.execute import cluster_runner_hlo
    except ImportError:
        return None
    from bench import harness
    c = ctx.config
    cfg = harness.system(c["system"]).program_config(c)
    s = c["n_shards"]
    return cluster_runner_hlo(cfg, s, c["lanes"] // s,
                              ctx.traffic["rounds_per_call"])
