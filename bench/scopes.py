"""Which named stage of the trainer each device operation belongs to.

The trainer runs its step under ``jax.named_scope``s: ``step.fwd_bwd``,
``step.exchange`` and ``step.optimizer``, and within the exchange
``exchange.rfft``, ``.select``, ``.pack``, ``.fold`` and ``.irfft``.  XLA
keeps each instruction's scope path in its ``op_name`` metadata
(``jit(step)/.../step.exchange/exchange.fold/...``).  JAX's own
``transpose(...)`` component marks the backward pass.

The TPU profiler writes no such path on its ``XLA Ops`` events, so a device
operation gets its path from the compiled step's HLO text
(``Run.hlo_text``), keyed by the instruction the event names.  An event
belongs to the step only if its whole instruction matches the step's: result
type, opcode and every ``%`` name.  The feed's module reuses names like
``%fusion.1`` with other operands, and those events stay out.

An instruction that the compiler made without metadata takes the path of
its fused body (not of the body's constants: XLA keeps one of equal
constants, named by whichever stage it kept), else of its operands (the
latest stage among them, the deepest path of that stage: an operation runs
after the stages it reads), else of the loop or call that holds it, else of
its first named user.  On the TPU this names the sort and the fusions of the
fold's scatter, which the scatter's expansion leaves unnamed.  A fusion that
XLA builds across two stages carries one of them.

Readers match whole ``/``-separated components of the path, so no
primitive's name can be taken for a scope.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Callable, Dict, List, Optional, Tuple

from bench import trace

FWD_BWD, EXCHANGE, OPTIMIZER = "step.fwd_bwd", "step.exchange", "step.optimizer"
STAGES = (FWD_BWD, EXCHANGE, OPTIMIZER)  # in the order the step runs them
RFFT, SELECT, PACK = "exchange.rfft", "exchange.select", "exchange.pack"
FOLD, IRFFT = "exchange.fold", "exchange.irfft"
BACKWARD_PREFIX = "transpose("

_HEAD = re.compile(r"^\s*(?:ROOT )?%([^\s=]+) = (.*?) ([a-z][a-z0-9\-]*)\(")
_COMPUTATION = re.compile(r"^(?:ENTRY )?%([^\s(]+) .*\{\s*$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_NAMES = re.compile(r"%([\w.\-]+)")
_CALLEES = re.compile(r"(?:calls|to_apply|body|condition|branch_computations)="
                      r"\{?%([\w.\-]+)")
_TAIL = re.compile(r", (?:metadata|backend_config|frontend_attributes|statistics)=")


@dataclasses.dataclass
class _Instr:
    key: Tuple
    op_name: Optional[str]
    operands: List[str]
    callees: List[str]
    computation: str
    root: bool


def _key(line: str):
    """(name, result type, opcode, every ``%`` name) of one instruction,
    from an HLO line or from a device event's name; ``None`` if neither."""
    head = _HEAD.match(line)
    if not head:
        return None
    body = _TAIL.split(line, maxsplit=1)[0]
    return (head.group(1), head.group(2), head.group(3), tuple(_NAMES.findall(body)))


def _latest_deepest(path: str) -> Tuple[int, int]:
    parts = path.split("/")
    stage = max((i for i, s in enumerate(STAGES) if s in parts), default=-1)
    return stage, len(parts)


class StepScopes:
    """The compiled step's instructions and the scope path of each."""

    def __init__(self, hlo_text: str):
        self.instrs: Dict[str, _Instr] = {}
        self.users: Dict[str, List[str]] = {}
        self.callers: Dict[str, str] = {}
        computation = ""
        for line in hlo_text.splitlines():
            head = _COMPUTATION.match(line)
            if head:
                computation = head.group(1)
                continue
            key = _key(line)
            if key is None:
                continue
            body = _TAIL.split(line, maxsplit=1)[0]
            args = body[body.index(key[2] + "(") + len(key[2]) + 1:]
            callees = _CALLEES.findall(body)
            operands = [n for n in _NAMES.findall(args) if n not in callees]
            op_name = _OP_NAME.search(line)
            self.instrs[key[0]] = _Instr(
                key, op_name.group(1) if op_name else None, operands, callees,
                computation, line.lstrip().startswith("ROOT "))
            for o in operands:
                self.users.setdefault(o, []).append(key[0])
            for c in callees:
                self.callers.setdefault(c, key[0])
        self.bodies: Dict[str, List[str]] = {}
        for name, ins in self.instrs.items():
            self.bodies.setdefault(ins.computation, []).append(name)
        self._paths: Dict[str, Optional[str]] = {}
        self.ops_key, self.ops = None, []  # step_ops of the last run read

    def _own(self, name: str, depth: int = 0) -> Optional[str]:
        """The instruction's own path, or its fused or called body's."""
        ins = self.instrs.get(name)
        if ins is None or depth > 8:
            return None
        if ins.op_name and ins.op_name.startswith("jit("):
            return ins.op_name
        for callee in ins.callees:
            # not a body's constants: XLA keeps one of equal constants, with
            # the name of whichever stage it kept
            names = [n for n in self.bodies.get(callee, [])
                     if self.instrs[n].operands or self.instrs[n].callees]
            for n in sorted(names, key=lambda n: not self.instrs[n].root):
                p = self._own(n, depth + 1)
                if p:
                    return p
        return None

    def path(self, name: str, depth: int = 0) -> Optional[str]:
        """Scope path of instruction ``name`` (module doc)."""
        if name in self._paths:
            return self._paths[name]
        self._paths[name] = None  # breaks cycles while resolving
        p = self._own(name)
        ins = self.instrs.get(name)
        if p is None and ins is not None and depth < 64:
            # XLA names an instruction it merged from several by what their
            # names share, which can be the module's root alone: hence the
            # deepest path
            found = [q for q in (self.path(o, depth + 1) for o in ins.operands) if q]
            p = max(found, key=_latest_deepest, default=None)
            caller = self.callers.get(ins.computation)
            if p is None and caller is not None:
                p = self.path(caller, depth + 1)
            for u in self.users.get(name, []) if p is None else []:
                p = self.path(u, depth + 1)
                if p:
                    break
        self._paths[name] = p
        return p

    def owns(self, event: trace.Event) -> bool:
        """Whether the event is one of the step's instructions: its name,
        result type, opcode and every ``%`` name match the step's HLO."""
        key = _key(event.name)
        ins = self.instrs.get(key[0]) if key else None
        return ins is not None and ins.key == key

    def parts(self, event: trace.Event) -> List[str]:
        """Components of the scope path of one of the step's events."""
        p = self.path(_key(event.name)[0])
        return p.split("/") if p else []


_CACHE: Dict[int, StepScopes] = {}


def for_run(run) -> StepScopes:
    k = hash(run.hlo_text)
    if k not in _CACHE:
        _CACHE.clear()
        _CACHE[k] = StepScopes(run.hlo_text)
    return _CACHE[k]


def under(scope: str, backward: Optional[bool] = None) -> Callable[[List[str]], bool]:
    """Predicate on a path's components: inside ``scope`` (and, with
    ``backward``, on that side of the transpose)."""
    def pred(parts):
        if scope not in parts:
            return False
        if backward is None:
            return True
        return any(p.startswith(BACKWARD_PREFIX) for p in parts) is backward
    return pred


def any_of(*scopes: str) -> Callable[[List[str]], bool]:
    return lambda parts: any(s in parts for s in scopes)


def step_ops(run) -> List[List[Tuple[trace.Event, List[str]]]]:
    """Per chip, the step's innermost operations in the traced window, each
    with the components of its scope path (kept for the run's other
    readers)."""
    scopes = for_run(run)
    key = (id(run.trace), run.window_ns)
    if scopes.ops_key != key:
        scopes.ops = [[(e, scopes.parts(e)) for e in trace.innermost(evs)
                       if scopes.owns(e)] for evs in run.device_events()]
        scopes.ops_key = key
    return scopes.ops


def stage_ms(run, pred: Callable[[List[str]], bool]) -> Optional[float]:
    """Device milliseconds per traced step of the operations whose path
    ``pred`` accepts, the mean over the cell's chips; ``None`` if the
    scope matched no operation."""
    if not run.steps or run.trace is None:
        return None
    per_chip, matched = [], False
    for ops in step_ops(run):
        hits = [e for e, parts in ops if pred(parts)]
        matched = matched or bool(hits)
        per_chip.append(trace.device_seconds(hits))
    if not matched:
        return None
    return 1e3 * sum(per_chip) / len(per_chip) / run.steps
