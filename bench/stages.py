"""The program's own spans and scopes in a traced run: the phases of each
``execute()`` call and the stages of the access round.

The program marks each call with host spans (``ditto.execute``, and inside
it ``ditto.execute.launch`` / ``.wait`` / ``.fetch``) and wraps the stages
of ``access_group`` in named scopes (``ditto.probe``, ``ditto.hit_update``,
``ditto.evict``, ``ditto.apply``, ``ditto.account``), which the compiler
keeps as each instruction's ``op_name``.  A TPU trace names a device
operation by its HLO instruction alone, so each runner operation's stage
is looked up in the runner's compiled HLO text, which the program gives
through ``repro.core.execute.runner_hlo``.  The names live here and are
not imported from the program: a renamed span or scope cannot change in
silence what a metric measures.

A program without any of these (one that predates them) reads None.  A
program that has them is held to every name: a traced call without one
of the three phase spans, or a runner without any one of the five stage
scopes, is an error, as ``Summary.runner_ns`` is for a renamed runner.
"""

from __future__ import annotations

import bisect
import re

from bench.tracing import merge

CALL = "bench.execute"
PHASE = "ditto.execute."
SCOPE = "ditto."
STAGES = ("probe", "hit_update", "evict", "apply", "account")

# "%fusion.505 = u32[8388608]{0:T(1024)} fusion(...": name, type, opcode
_INSTR = re.compile(r"\s*(?:ROOT )?(%[\w.\-]+) = (.*?) ([\w\-]+)\(")
_COMMENT = re.compile(r"/\*.*?\*/")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def _instr(line: str):
    """(name, type, opcode) of one HLO instruction, or None."""
    m = _INSTR.match(line)
    return m and (m.group(1), _COMMENT.sub("", m.group(2)), m.group(3))


def calls(s) -> list:
    return [x for x in s.spans if x[0] == CALL]


def instrumented(s) -> bool:
    """Whether the traced program marks its calls with ``ditto.execute``."""
    return any(x[0] == SCOPE + "execute" for x in s.host)


def phase_spans(s, phase: str) -> list:
    """The ``ditto.execute.<phase>`` host spans inside the traced calls;
    none for a program that does not mark its calls.  A program that marks
    its calls with ``ditto.execute`` but holds no span of this phase inside
    them is an error: the phase has another name."""
    within = calls(s)
    spans = [x for x in s.host if x[0] == PHASE + phase
             and any(b <= x[1] and x[2] <= e for _, b, e in within)]
    if within and not spans and instrumented(s):
        raise LookupError(
            f"the program marks its calls with {SCOPE}execute spans but "
            f"none of the traced calls holds a {PHASE}{phase} span: the "
            "program's phases have other names")
    return spans


def phase_ms_per_call(s, phase: str):
    """Summed duration of a phase's spans over the traced calls, per call,
    in ms; None where the program has no such span."""
    if s is None:
        return None
    spans, n = phase_spans(s, phase), len(calls(s))
    if not spans or not n:
        return None
    return sum(e - b for _, b, e in spans) / n / 1e6


def fullest(s):
    """The device with the most busy time in the traced window."""
    t0, t1 = s.window()
    return max(s.devices, key=lambda d: s.busy(d, t0, t1))


def op_stages(hlo_text: str) -> dict:
    """``{instruction: (type, opcode, stage)}`` of a compiled HLO module's
    text; ``stage`` is the first ``ditto.<stage>`` component of the
    instruction's ``op_name``, or None."""
    scopes = {SCOPE + st: st for st in STAGES}
    out = {}
    for line in hlo_text.splitlines():
        ins = _instr(line)
        if not ins:
            continue
        name = _OP_NAME.search(line)
        stage = next((scopes[c] for c in name.group(1).split("/")
                      if c in scopes), None) if name else None
        out[ins[0]] = (*ins[1:], stage)
    return out


def stage_ns(s, names, table: dict) -> dict:
    """Device time in the traced window of the runner modules (those whose
    names start with one of ``names``) on the chip where they take most,
    split by stage: ``{stage: ns}`` for the five stages and ``"outside"``
    for the runner's time in no stage's operation (the loop's own time, the
    code around the access round); together they are the runner's time.
    Each stage is the union of its operations' intervals inside the
    runner's.

    ``table`` is ``op_stages`` of the runner's HLO.  A runner operation
    that the table does not hold with the same type and opcode means the
    table is not of the program that ran: an error.  So is a runner in
    whose operations any one of the five stage scopes is missing."""
    t0, t1 = s.window()
    names = tuple(names)
    dev = max(s.devices, key=lambda d: s.module_ns(
        d, lambda n: n.startswith(names), t0, t1))
    mods = merge([m for m in dev.modules if m[0].startswith(names)], t0, t1)
    starts = [a for a, _ in mods]
    by_stage = {st: [] for st in STAGES}
    for op in dev.ops:
        i = bisect.bisect_right(starts, op[1]) - 1
        if i < 0 or op[1] >= mods[i][1]:
            continue  # not inside a runner module
        ins = _instr(op[0])
        got = table.get(ins[0]) if ins else None
        if got is None or got[:2] != ins[1:]:
            raise LookupError(
                f"runner operation {op[0][:120]!r} is not in the runner's "
                "HLO as the program gives it: the HLO is of another program")
        if got[2]:
            by_stage[got[2]].append(op)
    runner = sum(b - a for a, b in mods)
    missing = [SCOPE + st for st in STAGES if not by_stage[st]]
    if runner and missing:
        raise LookupError(
            "no runner operation carries the stage scope "
            + ", ".join(missing) + " in the traced window: the program's "
            "scopes have other names")
    out = {st: sum(e - b for a, z in mods for b, e in merge(ops, a, z))
           for st, ops in by_stage.items()}
    out["outside"] = runner - sum(out.values())
    return out


_LAST = [None, None]  # (Summary, its stage split), one run at a time


def round_stages_us(ctx):
    """``stage_ns`` of a traced run per traced round, in us; None where the
    run holds nothing to read or the program has no stage scopes."""
    s = ctx.traced
    if s is None or not s.devices or not ctx.traced_rounds:
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
    return stage_ns(s, ctx.config["runner_modules"], op_stages(text))


def runner_text(ctx):
    """The compiled HLO text of the runner the run's calls ran, from the
    program; None for a program that gives none and marks no call."""
    try:
        from repro.core.execute import runner_hlo
    except ImportError:
        if instrumented(ctx.traced):
            raise LookupError(
                "the program marks its calls with ditto.execute spans but "
                "has no repro.core.execute.runner_hlo to name its "
                "operations' stages") from None
        return None
    from bench import harness
    cfg = harness.system(ctx.config["system"]).program_config(ctx.config)
    return runner_hlo(cfg, ctx.config["lanes"],
                      ctx.traffic["rounds_per_call"])
