"""Device time by FL round stage, from a compiled program's HLO text and the
per-op device seconds of a traced window.

The program names each stage of its round with ``jax.named_scope``
(``fl.materialize``, ``fl.select``, ``fl.train``, ``fl.aggregate``,
``fl.eval``, ``fl.cluster``) and each layer of its model likewise; the
configuration lists the model's scopes under ``model_scopes``, each split
into its forward and backward pass, and under ``model_scopes_whole`` those
of them read whole, such as the optimizer's update.  A scope lands in the
``op_name`` metadata of every HLO instruction it lowers to, wrapped by the
transformations that ran over it (``vmap(fl.train)``,
``transpose(jvp(<scope>))`` on the backward pass).  A trace names each
device op by its instruction, so the instruction's scope is the op's.  The
harness passes the text of the executable that ran the window.  This module
parses the text itself and imports nothing of the program.

An instruction's stage is found by the first rule that applies:

1. its own ``op_name`` holds the scope (of ``;``-joined op_names, the most
   common);
2. an instruction that calls a computation (a fusion) takes the most common
   scope of the instructions in it;
3. a relayout with no scope of its own (a copy, reshape, transpose or bitcast
   that layout assignment inserted), or any op a compiler pass made without
   metadata, takes the scope that its operands' producers and all its users
   share, looking past such ops not yet resolved and past unscoped tuple
   plumbing (a loop carry's copy takes the scope of the op that feeds or
   reads it);
4. otherwise it is unscoped.

Control-flow containers (``while``, ``call``, ``conditional``) span the ops
of their bodies and are skipped, as in :mod:`bench.trace_reduce`.

The readers report only where the window ran on an accelerator: on a CPU run
:mod:`bench.trace_reduce` falls back to the ops' host-thread events, which
are no device time.  The attribution itself runs on either.
"""
from __future__ import annotations

import collections
import dataclasses
import re
import sys
from typing import Callable, Dict, Iterable, List, Optional, Tuple

from bench import trace_reduce

STAGES = ("materialize", "select", "train", "aggregate", "eval", "cluster")
_STAGE = re.compile(r"(?<![\w.])fl\.(%s)\b" % "|".join(STAGES))
_RELAYOUT = frozenset({"copy", "copy-start", "copy-done", "reshape",
                       "transpose", "bitcast"})
_PLUMBING = frozenset({"tuple", "get-tuple-element", "parameter"})
_INSTR = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s+=\s+(.*)$")
_OPERAND = re.compile(r"%([\w.\-]+)")
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
_OP_NAME = re.compile(r'\bop_name="((?:[^"\\]|\\.)*)"')


@dataclasses.dataclass
class Instr:
    computation: str
    opcode: str
    operands: List[str]
    calls: Optional[str]
    op_name: str


def _close(s: str, i: int) -> int:
    """Index just past the bracket group that opens at ``s[i]``."""
    depth = 0
    for j in range(i, len(s)):
        if s[j] in "([{":
            depth += 1
        elif s[j] in ")]}":
            depth -= 1
            if depth == 0:
                return j + 1
    return len(s)


def _parse_rhs(rhs: str) -> Optional[Tuple[str, str, str]]:
    """``shape opcode(operands), attrs`` → (opcode, operands, attrs)."""
    i = 0
    while i < len(rhs) and rhs[i] != " ":         # the shape: no space at depth 0
        i = _close(rhs, i) if rhs[i] in "([{" else i + 1
    m = re.match(r" ([\w\-]+)\(", rhs[i:])
    if not m:
        return None
    open_at = i + m.end() - 1
    end = _close(rhs, open_at)
    return m.group(1), rhs[open_at + 1:end - 1], rhs[end:]


def parse(hlo_text: str) -> Dict[str, Instr]:
    """Instruction name → :class:`Instr`, over every computation."""
    out: Dict[str, Instr] = {}
    comp = ""
    for line in hlo_text.splitlines():
        if line and not line[0].isspace():
            if line.rstrip().endswith("{"):
                head = line.split()[1] if line.startswith("ENTRY") else line.split()[0]
                comp = head.lstrip("%")
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        parsed = _parse_rhs(m.group(2))
        if parsed is None:
            continue
        opcode, operands, attrs = parsed
        calls = _CALLS.search(attrs)
        name = _OP_NAME.search(attrs)
        out[m.group(1)] = Instr(comp, opcode, _OPERAND.findall(operands),
                                calls.group(1) if calls else None,
                                name.group(1) if name else "")
    return out


def _most_common(keys: Iterable) -> Optional[object]:
    c = collections.Counter(k for k in keys if k is not None)
    return c.most_common(1)[0][0] if c else None


def stage_of(op_name: str) -> Optional[str]:
    """The innermost ``fl.<stage>`` one op_name names (through ``vmap(…)``,
    ``jvp(…)``, ``transpose(…)``), or None."""
    found = _STAGE.findall(op_name)
    return found[-1] if found else None


def model_scope_key(model_scopes: Iterable[str], whole: Iterable[str] = ()
                    ) -> Callable[[str], Optional[str]]:
    """op_name → the innermost of the configuration's model scopes that it
    names, or None: a scope in ``whole`` by its name alone, any other split
    by direction as ``<scope>:fwd`` / ``<scope>:bwd`` (a ``transpose(…)``
    wrapper is the backward pass)."""
    names = sorted(model_scopes, key=len, reverse=True)
    if not names:
        return lambda op_name: None
    whole = frozenset(whole)
    pattern = re.compile(r"(?<![\w.])(%s)\b" % "|".join(map(re.escape, names)))

    def key(op_name: str) -> Optional[str]:
        found = pattern.findall(op_name)
        if not found:
            return None
        if found[-1] in whole:
            return found[-1]
        return found[-1] + (":bwd" if "transpose(" in op_name else ":fwd")
    return key


def attribute(instrs: Dict[str, Instr],
              key_of: Callable[[str], Optional[str]] = stage_of
              ) -> Dict[str, Optional[str]]:
    """Instruction name → its key (a stage, by default) under rules 1–4."""
    def own(ins: Instr):
        return _most_common(key_of(p) for p in ins.op_name.split(";"))

    key = {n: own(ins) for n, ins in instrs.items()}
    members: Dict[str, List[str]] = collections.defaultdict(list)
    for n, ins in instrs.items():
        members[ins.computation].append(n)
    for n, ins in instrs.items():
        if key[n] is None and ins.calls is not None:
            key[n] = _most_common(key[m] for m in members.get(ins.calls, ()))
    users: Dict[str, List[str]] = collections.defaultdict(list)
    for n, ins in instrs.items():
        for o in ins.operands:
            users[o].append(n)
    # Rule 3's candidates: relayouts, and any op a compiler pass made
    # without metadata (on the CPU, the select-and-scatter expansion).
    pending = {n for n, ins in instrs.items() if key[n] is None
               and (ins.opcode in _RELAYOUT or not ins.op_name)}

    def near(n: str) -> Optional[List]:
        """Keys of the producers and users that decide ``n``, or None while
        one of them is unscoped for good.  Candidates not yet resolved and
        unscoped plumbing (tuple, get-tuple-element, parameter) are looked
        past."""
        out = []
        for m in [o for o in instrs[n].operands if o in instrs] + users[n]:
            if key[m] is not None:
                out.append(key[m])
            elif m not in pending and instrs[m].opcode not in _PLUMBING:
                return None
        return out

    changed = True
    while changed:                     # a chain of candidates resolves inward
        changed = False
        for n in sorted(pending):
            if key[n] is None:
                ks = near(n)
                if ks and len(set(ks)) == 1:
                    key[n] = ks[0]
                    changed = True
    return key


def seconds_by(op_s: Dict[str, float], key: Dict[str, Optional[str]]
               ) -> Tuple[Dict[str, float], float, float]:
    """(key → seconds, unscoped seconds, all seconds) over the
    non-container ops of ``op_s``; an op missing from the text is unscoped."""
    by: Dict[str, float] = {}
    unscoped = total = 0.0
    for name, s in op_s.items():
        if trace_reduce._CONTAINER.match(name):
            continue
        total += s
        k = key.get(name)
        if k is None:
            unscoped += s
        else:
            by[k] = by.get(k, 0.0) + s
    return by, unscoped, total


def _set_up_trace_s() -> Dict[str, float]:
    """Seconds of the program's ``trace:`` spans (``trace:trial``,
    ``trace:fl.<stage>``), the last event of each name: those of the cell's
    set-up.  Empty where the program records no such spans."""
    try:
        from repro.obs import events
    except ImportError:
        return {}
    return {e["name"]: round(e["dur"] / 1e6, 4) for e in events()
            if e.get("ph") == "X" and e["name"].startswith("trace:")}


def _on_accelerator() -> bool:
    """Whether the traced window ran on an accelerator (not the CPU)."""
    import jax
    return jax.default_backend() != "cpu"


def _log(msg: str) -> None:
    print(f"scopes: {msg}", file=sys.stderr, flush=True)


def read(ctx) -> Optional[dict]:
    """Seconds by stage and model scope for the traced window, computed once
    per ``ctx``; None where there is no trace, the window ran on the CPU,
    the harness passed no program text or trial-round count, or the program
    names no stage.

    ``ctx`` carries ``trace``, ``hlo_text`` (the executable's text),
    ``trial_rounds`` (the window's, as the harness counted them) and
    ``config`` (whose ``model_scopes`` and ``model_scopes_whole``, where
    given, are read too)."""
    if "_scopes" in ctx:
        return ctx["_scopes"]
    ctx["_scopes"] = None
    if not ctx.get("trace") or not _on_accelerator():
        return None
    traced = _set_up_trace_s()
    text, rounds = ctx.get("hlo_text"), ctx.get("trial_rounds")
    if not text or not rounds:
        return None
    instrs = parse(text)
    op_s = ctx["trace"].op_s
    by_stage, unscoped, total = seconds_by(op_s, attribute(instrs))
    if not by_stage:
        _log("the program names no FL round stage")
        return None
    cfg = ctx.get("config", {})
    model_of = model_scope_key(cfg.get("model_scopes", ()),
                               cfg.get("model_scopes_whole", ()))
    by_model, _, _ = seconds_by(op_s, attribute(instrs, model_of))
    missing = sum(s for n, s in op_s.items() if n not in instrs
                  and not trace_reduce._CONTAINER.match(n))
    out = {"trial_rounds": rounds, "stage_s": by_stage,
           "unscoped_s": unscoped, "total_s": total, "model_s": by_model}
    ctx["_scopes"] = out

    def per_round_ms(d):
        return {k: round(v / rounds * 1e3, 4)
                for k, v in sorted(d.items(), key=lambda kv: -kv[1])}
    _log(f"device ms per trial-round ({rounds} trial-rounds) by stage "
         f"{per_round_ms(by_stage)}, unscoped {unscoped / rounds * 1e3:.4f}; "
         f"by model scope {per_round_ms(by_model)}; op seconds not in the "
         f"program text {missing!r} of {total!r}; set-up trace seconds "
         f"{traced}")
    return out


def stage_ms(ctx, stage: str) -> Optional[float]:
    """Device ms per trial-round of one stage (0 where the program names
    stages but none of this one ran in the window)."""
    r = read(ctx)
    if r is None:
        return None
    return r["stage_s"].get(stage, 0.0) / r["trial_rounds"] * 1e3
