"""The programs a process built, and which compiled instruction of each
belongs to which plan node.

Every operation a plan node emits carries the node's scope in its name
(``n<ordinal>:<kind>``, exec/executor.py ``Lowerer.lower``), and XLA keeps
that name through optimisation as each compiled instruction's
``metadata={op_name="jit(run)/n0:limit/n1:sort/…/sort"}``. A device
profile names the INSTRUCTION that ran (``fusion.258``, ``while.15``), so
the way from a profile's event to a plan node is the compiled module's own
text. This module keeps that way open and does nothing else: no span, no
clock, no exporter.

- ``jit(fn, nodes, what)``: ``jax.jit`` of a program where it is built
  (``compile_plan``, ``compile_distributed``, the tiled ``_compile``s, the
  dispatcher's stacked programs), registered once, weakly. The entry keeps
  the statement's text, the plan's node titles by ordinal and the abstract
  inputs of each trace of the function, taken INSIDE the traced function:
  once a signature, never at a launch.
- ``instruction_map(entry)``: on demand, {instruction → (ordinal, kind,
  scope path)} from the optimised module of the very executable that ran
  (JAX's in-memory caches answer ``lower(spec).compile()`` with it);
  never a cold compile of a large program: ``None``, "map: unavailable".
- ``find`` / ``attribute``: a profile's module event and the instructions
  seen under it → the registered program → seconds by plan node.
- ``snapshot()``: ``meta "programs"``.

Rules of attribution (docs/DESIGN.md "Observability"): an instruction is
charged with its own ``op_name`` (inside a loop body too); a fusion whose
own name holds no node with its root's, else with the name most of its
fused instructions carry; an instruction with no name at all (the
compiler's own: a copy, the pieces of a rewrite) with the first of its
operands that has a node, else with the loop whose body or condition it
lies in; the innermost ``n<k>:`` of a name is the node
(SELF time, as EXPLAIN ANALYZE counts rows); a name with no node goes to
its unnumbered scope (``UNNUMBERED``), a program input's name to ``input``,
anything else to ``unscoped``.
"""

from __future__ import annotations

import functools
import re
import threading
import weakref
from collections import Counter
from typing import NamedTuple

# scopes of the same form without an ordinal: what a program does outside
# any plan node (exec/executor.py pack_answer, the check flags' reduction,
# a tiled step's fold of a tile into its carry)
UNNUMBERED = ("answer", "checks", "tile:merge")
# what runs on a program's input before any node reads it, named by the
# input (``tables['lineitem']['l_orderkey']``): on a TPU an int64 column's
# split into two words, a column's copy into fast memory
INPUT = "input"
UNSCOPED = "unscoped"

_MAX_ENTRIES = 512      # live entries kept; the oldest leaves first
# a program with no executable in JAX's caches is compiled for its map
# only if its lowered text is at most this long (a compile of seconds)
_SMALL_MODULE_CHARS = 200_000

_lock = threading.Lock()
_entries: list = []         # oldest first; the dead leave at a registration
_seq = 0


class Where(NamedTuple):
    """Where an instruction's time goes: a plan node (``ordinal`` and its
    ``kind``), or no node (``ordinal`` None; ``kind`` one of
    ``UNNUMBERED`` or ``unscoped``); ``path`` is the whole scope path."""
    ordinal: int | None
    kind: str
    path: str


class ProgramMap(NamedTuple):
    module: str                 # the HloModule's name
    where: dict                 # instruction name -> Where
    shapes: dict                # instruction name -> result shapes


class Entry:
    """One registered program. ``signatures`` grows when the function is
    traced; ``maps`` is filled by ``instruction_map``."""

    __slots__ = ("seq", "what", "sql", "nodes", "signatures", "fn",
                 "maps", "__weakref__")

    def __init__(self, what: str, sql: str, nodes: dict):
        self.seq = 0
        self.what = what
        self.sql = sql
        self.nodes = nodes              # ordinal -> node.title()
        self.signatures: list = []      # trees of ShapeDtypeStruct
        self.fn = lambda: None          # weakref to the jitted function
        self.maps: dict = {}            # signature index -> ProgramMap|None

    def title(self, ordinal) -> str:
        return self.nodes.get(ordinal, "")

    def describe(self) -> dict:
        def state(i):
            if i not in self.maps:
                return "not built"
            m = self.maps[i]
            return "unavailable" if m is None \
                else f"{len(m.where)} instructions"

        return {"program": self.seq, "what": self.what, "sql": self.sql,
                "nodes": {str(k): v for k, v in sorted(self.nodes.items())},
                "traces": len(self.signatures),
                "live": self.fn() is not None,
                "maps": [state(i) for i in range(len(self.signatures))]}


def current_sql() -> str:
    """The text (200 characters, as ``Trace.sql``) of the statement the
    calling thread serves, "" outside one."""
    from cloudberry_tpu.lifecycle import current_handle

    h = current_handle()
    if h is None:
        return ""
    trace = getattr(h, "trace", None)
    if trace is not None:
        return trace.sql
    log = getattr(h, "log", None)
    return log.sql_of(h.statement_id)[:200] if log is not None else ""


def jit(fn, nodes: dict, what: str, **jit_kwargs):
    """``jax.jit(fn, **jit_kwargs)``, registered: ``nodes`` are the plan's
    node titles by ordinal (exec/executor.py ``node_titles``), ``what``
    says which program of a statement this is. The function is traced
    through a wrapper that notes each trace's abstract inputs; a launch
    of a traced signature never enters it."""
    import jax

    entry = Entry(what, current_sql(), nodes)

    @functools.wraps(fn)
    def traced(*args):
        _note_signature(entry, args)
        return fn(*args)

    jitted = jax.jit(traced, **jit_kwargs)
    entry.fn = weakref.ref(jitted)
    global _seq
    with _lock:
        _seq += 1
        entry.seq = _seq
        _entries[:] = [e for e in _entries if e.fn() is not None
                       ][1 - _MAX_ENTRIES:] + [entry]
    return jitted


def _note_signature(entry: Entry, args) -> None:
    import jax

    def abstract(x):
        aval = getattr(x, "aval", None)
        return jax.ShapeDtypeStruct(
            x.shape, x.dtype, weak_type=getattr(aval, "weak_type", False))

    sig = jax.tree_util.tree_map(abstract, args)
    if sig not in entry.signatures:
        entry.signatures.append(sig)


def entries() -> list:
    """The registered programs whose function is alive, oldest first."""
    with _lock:
        return [e for e in _entries if e.fn() is not None]


def snapshot(limit: int = 64) -> dict:
    """``meta "programs"``: the newest live entries, newest first."""
    live = entries()[::-1][:max(1, limit)]
    return {"programs": [e.describe() for e in live]}


# ------------------------------------------------------------ the map


_INSTR = re.compile(r"^\s+(ROOT )?%?([\w.\-]+) = (.*)$")
_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) .*\{\s*$")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
_MENTIONED = re.compile(r"%([\w.\-]+)")
_NODE = re.compile(r"(?:^|[/(])n(\d+):([a-z_:]+?)(?=[/)]|$)")
_SHAPE = re.compile(r"\b[a-z]+[0-9]*\[[0-9,]*\]")
_LINE_NAME = re.compile(r"^%?([\w.\-]+)(?: = (.*))?$", re.DOTALL)


def where_of(path: str) -> Where:
    """The node an ``op_name`` belongs to: its innermost ``n<k>:<kind>``,
    else its innermost unnumbered scope, else ``unscoped``."""
    hits = _NODE.findall(path)
    if hits:
        return Where(int(hits[-1][0]), hits[-1][1], path)
    if path and not path.startswith(("jit(", "pjit(")):
        return Where(None, INPUT, path)
    for part in reversed(re.split(r"[/()]", path)):
        if part in UNNUMBERED:
            return Where(None, part, path)
    return Where(None, UNSCOPED, path)


def _result_shapes(rest: str) -> tuple | None:
    """The shapes (``u32[6029312]``) of an instruction's result, from
    what follows ``name = `` in its line: a tuple's parenthesis, or the
    text before the opcode. None where the line was cut inside them."""
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                return tuple(_SHAPE.findall(rest[:i + 1]))
        return None
    first, _, more = rest.partition(" ")
    return tuple(_SHAPE.findall(first)) if more else None


def event_instruction(name: str) -> tuple:
    """(instruction name, result shapes or None) of a profile's event: a
    TPU names an operation by its whole HLO line (``%fusion.258 =
    u32[6029312]{…} fusion(…)``), the CPU by the instruction alone."""
    m = _LINE_NAME.match(name.strip())
    if m is None:
        return name, None
    rest = m.group(2)
    return m.group(1), (_result_shapes(rest) if rest else None)


def parse_module(text: str) -> tuple:
    """(module name, {instruction: Where}, {instruction: result shapes})
    of an optimised module's text."""
    head = re.match(r"HloModule ([\w.\-]+)", text)
    module = head.group(1) if head else ""
    own: dict = {}          # instruction -> op_name
    home: dict = {}         # instruction -> the computation it lies in
    calls: dict = {}        # fusion instruction -> fused computation
    caller: dict = {}       # computation -> the instruction that runs it
    roots: dict = {}        # computation -> its ROOT's op_name
    inside: dict = {}       # computation -> op_names of its instructions
    operands: dict = {}     # an unnamed instruction -> what it mentions
    shapes: dict = {}
    comp = None
    for line in text.splitlines():
        m = _INSTR.match(line)
        if m is None:
            c = _COMPUTATION.match(line)
            if c is not None:
                comp = c.group(1)
                inside[comp] = []
            continue
        root, name, rest = m.groups()
        op = _OP_NAME.search(rest)
        path = op.group(1) if op else ""
        own[name], home[name] = path, comp
        shapes[name] = _result_shapes(rest)
        mentioned = _MENTIONED.findall(rest)
        for other in mentioned:     # (a callee is printed before its caller)
            if other in inside:
                caller[other] = name
        if path:
            inside[comp].append(path)
            if root:
                roots[comp] = path
        else:
            operands[name] = mentioned
        if " fusion(" in rest:
            called = _CALLS.search(rest)
            if called:
                calls[name] = called.group(1)
    where: dict = {}
    for name, path in own.items():      # (in the text's order: an
        w = where_of(path)              # operand comes before its user)
        if w.ordinal is None and name in calls:
            # a fusion with no node of its own: its root's, else the
            # name most of what it fused carries
            fused = calls[name]
            alts = (where_of(p) for p in (roots.get(fused, ""), *(
                p for p, _ in Counter(inside.get(fused, ())).most_common())))
            w = next((a for a in alts if a.ordinal is not None), w)
        if w.ordinal is None and not path:
            # no name at all: the compiler's own (a copy, the pieces of
            # a rewrite): with the first operand that has a node (or is
            # an input)
            known = [(where[o], o) for o in operands.get(name, ())
                     if o in where and where[o].kind != UNSCOPED]
            for src, operand in sorted(
                    known, key=lambda so: so[0].ordinal is None)[:1]:
                w = Where(src.ordinal, src.kind,
                          f"{src.path} <- %{operand}")
        where[name] = w
    for name, w in where.items():
        # ... else, inside a loop's body or condition: the loop's
        at = name
        while w.kind == UNSCOPED and not own[name] \
                and home[at] in caller:
            at = caller[home[at]]
            if where[at].ordinal is not None:
                w = where[name] = Where(
                    where[at].ordinal, where[at].kind,
                    f"{where[at].path} <- %{at}")
    return module, where, shapes


def _compiled_for(entry: Entry, signature):
    """The compiled program of one traced signature, or None where it
    would take a cold compile of a large program. ``lower`` of the
    abstract inputs a trace noted finds the trace, the lowering and the
    executable of the launches in JAX's in-memory caches: nothing is
    traced, compiled or loaded again."""
    fn = entry.fn()
    if fn is None:
        return None
    lowered = fn.lower(*signature)
    ran = getattr(getattr(lowered, "_lowering", None), "_executable", None)
    if ran is None and len(lowered.as_text()) > _SMALL_MODULE_CHARS:
        return None
    return lowered.compile()


def instruction_map(entry: Entry, index: int = 0) -> ProgramMap | None:
    """The map of the ``index``-th traced signature of ``entry``, built
    once; None: unavailable (the readers count the program's time as
    unattributed)."""
    if index in entry.maps:
        return entry.maps[index]
    try:
        compiled = _compiled_for(entry, entry.signatures[index])
        out = None if compiled is None \
            else ProgramMap(*parse_module(compiled.as_text()))
    except Exception:
        out = None
    entry.maps[index] = out
    return out


def all_maps() -> list:
    """[(entry, ProgramMap)] of every live entry's traced signatures
    that offer a map."""
    out = []
    for e in entries():
        for i in range(len(e.signatures)):
            m = instruction_map(e, i)
            if m is not None:
                out.append((e, m))
    return out


def holders(module: str, seen: dict, maps: list) -> list:
    """The distinct programs among ``maps`` named as the profile's module
    event ``module`` is (``jit__lambda(8371619553279078626)``: the number
    is the profiler's own, no executable offers it) whose text holds
    every instruction ``seen`` ({name: result shapes or None}) with the
    same shapes. (A program registered twice, two sessions' or two traces
    of one module, is one program.)"""
    base = re.sub(r"\(\d+\)$", "", module.strip())
    out: list = []
    for e, m in maps:
        if seen and m.module == base and all(
                name in m.where
                and (shape is None or m.shapes[name] == shape)
                for name, shape in seen.items()) \
                and not any(m.where == d.where for _, d in out):
            out.append((e, m))
    return out


def find(module: str, seen: dict, maps: list | None = None):
    """The (entry, ProgramMap) a profile's module event belongs to: the
    ONE program that holds what was ``seen`` under the event
    (``holders``); none or several is None (never by an instruction's
    name alone: ``fusion.7`` is in every program)."""
    held = holders(module, seen, all_maps() if maps is None else maps)
    return held[0] if len(held) == 1 else None


def attribute(pmap: ProgramMap | None, ops) -> dict:
    """{(ordinal | None, kind): seconds} of ``ops``, [(instruction name,
    seconds)] that ran under ONE program: each instruction's seconds to
    its node by ``pmap``; an instruction the map does not hold, and every
    one where there is no map, to ``(None, "unscoped")``."""
    out: dict = {}
    where = pmap.where if pmap is not None else {}
    for name, seconds in ops:
        w = where.get(name)
        key = (w.ordinal, w.kind) if w is not None else (None, UNSCOPED)
        out[key] = out.get(key, 0.0) + seconds
    return out
