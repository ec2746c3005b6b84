"""Which of the program's named scopes each compiled operation ran under.

The program names its device work with ``jax.named_scope`` (``SCOPES``:
``repro.core.moe``, ``repro.models.model``, ``repro.train.step``), and
the compiler keeps each scope in the ``op_name`` metadata of the
operations it compiles the scope's work into.  ``op_names`` reads the
compiled step's HLO text (``compiled.as_text()``) into {instruction
name: op_name}; a fusion without metadata of its own takes its fused
computation's.  The instruction names are the names the device trace
gives its operations, so ``device_ms`` puts every operation's self time
(``op_ms``) down to its scope.

An op_name is a path such as
``jit(train_step)/transpose(jvp())/while/body/checkpoint/attention/dot``.
Its innermost scope of ``SCOPES`` is the scope; the phase is ``fwd``,
``bwd`` where a ``transpose(...)`` component comes at or before the
scope (the work of the scope's AD transpose), or ``remat`` where a
``rematted_computation`` comes after the last such component (the
forward recomputed in the backward).  spRS, the sparse reduce-scatter,
is ``spag.bwd`` and ``sprs``.
"""
from __future__ import annotations

import re
from typing import Dict, Optional

SCOPES = ("gate", "dispatch", "combine", "spag", "sprs", "expert_ffn",
          "attention", "lm_head", "optimizer")
UNSCOPED = "unscoped"
# a layer of the program by the scopes its device work runs under
LAYERS = {"dispatch": ("gate", "dispatch", "combine"),
          "materialize": ("spag", "sprs"),
          "optimizer": ("optimizer",)}

_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%(\S+)\s")
_INSTRUCTION = re.compile(r"^\s+(ROOT\s+)?%(\S+)\s=\s")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_CALLED = re.compile(r"(?:calls|body|condition|to_apply)=%([^\s,}]+)")
_WRAPPED = re.compile(r"^\w+\((.*)\)$")


def _base(component: str) -> str:
    """``attention`` from ``transpose(jvp(attention))``; a ``jit(...)``
    component names a function, not a scope."""
    if component.startswith("jit("):
        return ""
    while True:
        m = _WRAPPED.match(component)
        if not m:
            return component
        component = m.group(1)


def scope_of(op_name: str) -> Optional[str]:
    """``<scope>.<phase>`` of an op_name, or None outside every scope."""
    parts = op_name.split("/")
    at = next((i for i in range(len(parts) - 1, -1, -1)
               if _base(parts[i]) in SCOPES), None)
    if at is None:
        return None
    phase = "fwd"
    for i, p in enumerate(parts[:at + 1]):
        if "transpose(" in p:
            phase = "bwd"
        elif p == "rematted_computation" and phase == "bwd" and i < at:
            phase = "remat"
    return f"{_base(parts[at])}.{phase}"


def op_names(hlo_text: str) -> Dict[str, str]:
    """{instruction name: op_name} over every computation of the HLO
    text.  An instruction without metadata of its own takes, in this
    order: the first scoped op_name in a computation it calls, looking
    into the computations those call in turn (a fusion's); the op_name
    of the instruction that calls its own computation (a loop body that
    the compiler wrote, such as an expanded scatter, takes its loop's);
    the root's op_name of a computation it calls."""
    own: Dict[str, str] = {}
    home: Dict[str, str] = {}            # instruction -> its computation
    calls: Dict[str, list] = {}          # instruction -> computations
    body: Dict[str, list] = {}           # computation -> instructions
    root: Dict[str, str] = {}            # computation -> root's op_name
    comp = None
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m is None:
            c = _COMPUTATION.match(line)
            if c:
                comp = c.group(1)
                body[comp] = []
            continue
        name = m.group(2)
        home[name] = comp
        body[comp].append(name)
        called = _CALLED.findall(line)
        if called:
            calls[name] = called
        op = _OP_NAME.search(line)
        if op:
            own[name] = op.group(1)
            if m.group(1):
                root[comp] = op.group(1)
    caller = {c: name for name, cs in calls.items() for c in cs}
    scoped: Dict[str, Optional[str]] = {}

    def scoped_in(c):
        if c not in scoped:
            scoped[c] = None            # a cycle finds nothing
            for name in body.get(c, ()):
                op = own.get(name)
                if op is None or not scope_of(op):
                    op = next(filter(None, map(scoped_in,
                                               calls.get(name, ()))), None)
                if op is not None:
                    scoped[c] = op
                    break
        return scoped[c]

    def resolve(name, seen=()):
        if name in own:
            return own[name]
        cs = calls.get(name, ())
        got = next(filter(None, map(scoped_in, cs)), None)
        up = caller.get(home.get(name))
        if got is None and up is not None and up not in seen:
            got = resolve(up, seen + (name,))
        return got or next((root[c] for c in cs if c in root), None)

    out = {}
    for name in home:
        op = resolve(name)
        if op is not None:
            out[name] = op
    return out


def scope_map(names: Dict[str, str]) -> Dict[str, str]:
    """{instruction name: ``<scope>.<phase>``} of the scoped instructions
    of ``op_names``."""
    out = {}
    for name, op in names.items():
        key = scope_of(op)
        if key:
            out[name] = key
    return out


def op_ms(events, steps: int) -> Dict[str, float]:
    """Device ms a step per operation: its self time in the window
    (``tracereduce.Events``), mean over devices, over ``steps``."""
    out: Dict[str, float] = {}
    for dev in events.devices:
        for name, t in events.self_times(dev).items():
            out[name] = out.get(name, 0.0) + t
    per = 1e-6 / len(events.devices) / steps
    return {k: v * per for k, v in out.items()}


def device_ms(per_op: Dict[str, float], smap: Dict[str, str]
              ) -> Dict[str, float]:
    """``op_ms`` summed per ``<scope>.<phase>``, and ``unscoped``."""
    out: Dict[str, float] = {}
    for name, t in per_op.items():
        key = smap.get(name, UNSCOPED)
        out[key] = out.get(key, 0.0) + t
    return out


def scope_sum(ms: Dict[str, float], *scopes: str) -> float:
    """The ms of ``device_ms`` under any phase of ``scopes``."""
    return sum(v for k, v in ms.items() if k.split(".")[0] in scopes)


def sprs_ms(ms: Dict[str, float]) -> float:
    """The ms of ``device_ms`` that spRS takes: ``spag`` transposed, and
    ``sprs``."""
    return ms.get("spag.bwd", 0.0) + scope_sum(ms, "sprs")
