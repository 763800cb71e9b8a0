"""From a compiled program's HLO instructions back to the graph's nodes.

``executor._trace_graph`` traces every graph node under
``jax.named_scope(<node>)`` and ``module/fused.py`` puts the rest of the
step under scopes of its own (``mxtpu.update/<parameter>``,
``mxtpu.head_grad``, ``mxtpu.health``), so every instruction of the
optimised HLO carries, in its ``op_name`` metadata, the path it was traced
under: ``jit(mxtpu_fused_step)/jvp(l3_attn)/...`` forward,
``.../transpose(jvp(l3_attn))/...`` backward. This module is the
repository's one reader of HLO text (``parse``) and turns a module into a
table ``{instruction name: OpScope}`` (``build_table``): the name is what
a profiler trace's ``XLA Ops`` line prints (``fusion.717``,
``mxtpu_flash_bwd.2``), the scope is the node, its operator and model
block (``mx.AttrScope(block=...)``) from the Symbol the program was built
from (``symbol_scopes``), and the phase.

A fusion takes the scope of the matmul, convolution or custom call it
holds, else of its root; ``mixed`` says it holds instructions of more
than one node. An instruction whose metadata names no node and none of the
step's own scopes (XLA made it: a layout copy, a prefetch's ``copy-done``,
the ``ragged-dot-none`` calls a grouped matmul becomes) adopts the node its
scoped neighbours agree on (``_adopt``), else it is ``unscoped``.

The metadata is the compiled executable's own. JAX's persistent compile
cache leaves metadata out of its key, so an executable loaded from an entry
that a program without the step's scopes wrote carries that program's
``op_name``s: node scopes as ever, the updates ``unscoped``.
"""
from __future__ import annotations

import re
from collections import namedtuple

__all__ = ["Instr", "Module", "OpScope", "UNSCOPED", "parse", "classify",
           "build_table", "node_scopes", "symbol_scopes", "instruction_name",
           "UPDATE", "HEAD_GRAD", "HEALTH", "PHASES"]

#: the step's own scopes (module/fused.py): not graph nodes
UPDATE, HEAD_GRAD, HEALTH = "mxtpu.update", "mxtpu.head_grad", "mxtpu.health"
PHASES = ("forward", "backward", "update", "unscoped")

Instr = namedtuple("Instr", "name opcode shape operands op_name called root")
OpScope = namedtuple("OpScope", "node operator block phase mixed")
UNSCOPED = OpScope("", "", "", "unscoped", False)

#: opcodes whose scope a fusion takes before its root's
_HEROES = ("dot", "convolution", "custom-call", "ragged-dot")
#: instructions that compute nothing: not counted towards ``mixed``
_PLUMBING = ("parameter", "constant", "tuple", "get-tuple-element", "bitcast")

_HEADER = re.compile(r"^(ENTRY\s+)?%?([\w.$-]+)\s*\(.*\{\s*$")
_INSTR = re.compile(r"^\s+(ROOT\s+)?%?([\w.$-]+) = ")
_OPCODE = re.compile(r"\s*([\w-]+)\(")
_OPERAND = re.compile(r"%([\w.$-]+)")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_CALLED = re.compile(
    r"\b(?:calls|to_apply|body|condition|true_computation|false_computation"
    r"|branch_computations|called_computations)=(\{[^}]*\}|%?[\w.$-]+)")
_WRAPPED = re.compile(r"^([\w.]+)\((.*)\)$")


class Module:
    """One parsed ``HloModule``: ``computations`` maps a computation's name
    to its instructions in the text's order, ``entry`` names the entry."""

    def __init__(self, name, computations, entry):
        self.name, self.computations, self.entry = name, computations, entry

    def instructions(self):
        for comp, body in self.computations.items():
            for ins in body:
                yield comp, ins


def _group_end(line, at):
    """Index just past the parenthesised group that starts at ``at`` (a
    tuple shape's or an operand list's parentheses nest, and hold
    ``/*index=5*/`` comments), or past the word that does."""
    if line[at] != "(":
        end = line.find(" ", at)
        return len(line) if end < 0 else end
    depth = 0
    for i in range(at, len(line)):
        c = line[i]
        if c == "(":
            depth += 1
        elif c == ")":
            depth -= 1
            if depth == 0:
                return i + 1
    return len(line)


def parse(text):
    """``Module`` of an HLO text (``Compiled.as_text()``,
    ``HloModule.to_string()``; the first module if the text holds
    several)."""
    name, entry, comps, cur = "", "", {}, None
    for line in text.splitlines():
        if cur is None:
            if line.startswith("HloModule "):
                if name:
                    break
                name = line[10:].split(",")[0].strip()
                continue
            m = _HEADER.match(line)
            if m and " = " not in line.split("(", 1)[0]:
                cur = comps.setdefault(m.group(2), [])
                if m.group(1):
                    entry = m.group(2)
            continue
        if line.startswith("}"):
            cur = None
            continue
        m = _INSTR.match(line)
        if m is None:
            continue
        end = _group_end(line, m.end())
        op = _OPCODE.match(line, end)
        args = _group_end(line, op.end() - 1) if op else end
        meta = _OP_NAME.search(line, args)
        called = []
        for c in _CALLED.findall(line, args):
            called += [s.strip().lstrip("%") for s in c.strip("{}").split(",")
                       if s.strip()]
        cur.append(Instr(m.group(2), op.group(1) if op else "",
                         line[m.end():end],
                         tuple(_OPERAND.findall(line, end, args)),
                         meta.group(1).replace("\\'", "'") if meta else "",
                         tuple(called), bool(m.group(1))))
    return Module(name, comps, entry)


def instruction_name(event_name):
    """What a trace's ``XLA Ops`` event is called in the table: the events
    are named by their HLO text, ``%fusion.61 = bf16[...] fusion(...)``."""
    return event_name.split(" = ", 1)[0].lstrip("%").strip()


# ------------------------------------------------------------ the scopes
def node_scopes(topo):
    """{id(node): scope} of the operator nodes of a topological order: the
    node's name, or, where it has none or an earlier node has taken it,
    ``<name or operator>.<index in the order>``: unique to the node."""
    seen, out = set(), {}
    for i, node in enumerate(topo):
        if node.is_variable:
            continue
        scope = node.name or node.op.name
        if not node.name or scope in seen:
            scope = "%s.%d" % (scope, i)
        seen.add(scope)
        out[id(node)] = scope
    return out


def symbol_scopes(symbol):
    """What a table takes from the Symbol a program was built from:
    ``{"nodes": {scope: (operator, block)}, "params": {variable: scope of
    its first consumer}}``. Plain strings: holds nothing of the graph."""
    topo = symbol._topo()
    scopes = node_scopes(topo)
    nodes, params = {}, {}
    for node in topo:
        if node.is_variable:
            continue
        scope = scopes[id(node)]
        nodes[scope] = (node.op.name, node._extra_attrs.get("__block__", ""))
        for src, _ in node.inputs:
            if src.is_variable:
                params.setdefault(src.name, scope)
    return {"nodes": nodes, "params": params}


def _elements(op_name):
    """The path's elements: split at ``/`` outside parentheses."""
    out, depth, start = [], 0, 0
    for i, c in enumerate(op_name):
        if c == "(":
            depth += 1
        elif c == ")":
            depth -= 1
        elif c == "/" and depth == 0:
            out.append(op_name[start:i])
            start = i + 1
    out.append(op_name[start:])
    return out


def _unwrap(element):
    """``transpose(jvp(l3_attn))`` -> ({"transpose", "jvp"}, "l3_attn")."""
    wrappers = set()
    while True:
        m = _WRAPPED.match(element)
        if m is None:
            return wrappers, element
        wrappers.add(m.group(1))
        element = m.group(2)


def classify(op_name, scopes=None):
    """``OpScope`` of one instruction's ``op_name``. The first element of
    the path that names a node of ``scopes`` (or one of the step's own
    scopes) decides; ``transpose`` around it or before it, or a
    ``rematted_computation`` before it, makes it backward. Without
    ``scopes`` the first element that is no ``jit(...)`` and no transform's
    empty shell is taken for a node."""
    if not op_name:
        return UNSCOPED
    nodes = scopes["nodes"] if scopes else None
    params = scopes["params"] if scopes else {}
    backward = False
    parts = _elements(op_name)
    for i, part in enumerate(parts):
        wrappers, inner = _unwrap(part)
        if "transpose" in wrappers or inner == "rematted_computation":
            backward = True
        if inner == UPDATE:
            param = parts[i + 1] if i + 1 < len(parts) else ""
            node = params.get(param, "")
            op, block = (nodes or {}).get(node, ("", ""))
            return OpScope(node or "%s/%s" % (UPDATE, param), op, block,
                           "update", False)
        if inner == HEALTH:
            return OpScope(HEALTH, "", "", "update", False)
        if inner == HEAD_GRAD:
            return OpScope(HEAD_GRAD, "", "", "backward", False)
        if nodes is None:
            if not inner or wrappers & {"jit", "pjit"} \
                    or inner in ("checkpoint", "rematted_computation"):
                continue
            return OpScope(inner, "", "", "backward" if backward
                           else "forward", False)
        if inner in nodes:
            op, block = nodes[inner]
            return OpScope(inner, op, block, "backward" if backward
                           else "forward", False)
    return UNSCOPED


def build_table(module, scopes=None):
    """{instruction name: OpScope} for every instruction of every
    computation of ``module`` (a ``Module``, or HLO text)."""
    if isinstance(module, str):
        module = parse(module)
    comps = module.computations
    memo = {}

    def of(op_name):
        got = memo.get(op_name)
        if got is None:
            got = memo[op_name] = classify(op_name, scopes)
        return got

    def fused(ins):
        """A fusion's scope: its computation's matmul, convolution or
        custom call, else its root, else its own metadata, else the first
        scoped instruction it holds."""
        hero = root = first = None
        nodes_seen = set()
        todo = list(ins.called)
        while todo:
            for inner in comps.get(todo.pop(), ()):
                if inner.opcode == "fusion":
                    todo.extend(inner.called)
                if inner.opcode in _PLUMBING:
                    continue
                got = of(inner.op_name)
                if got is UNSCOPED:
                    continue
                nodes_seen.add(got.node)
                first = first or got
                if hero is None and inner.opcode in _HEROES:
                    hero = got
                if inner.root:
                    root = got
        own = of(ins.op_name)
        got = hero or root or (own if own is not UNSCOPED else first)
        if got is None:
            return UNSCOPED
        return got._replace(mixed=len(nodes_seen) > 1)

    table = {}
    for _, ins in module.instructions():
        table[ins.name] = fused(ins) if ins.opcode == "fusion" \
            else of(ins.op_name)
    for body in comps.values():
        _adopt(body, table)
    return table


_ORDER = {"forward": 0, "backward": 1, "update": 2}
_REACH = 3      # instructions without a scope crossed on the way to one


def _adopt(body, table):
    """An instruction of one computation whose metadata names no node (XLA
    made it: a layout copy, a prefetch's ``copy-done``, a grouped matmul's
    ``ragged-dot-none``) adopts the node on which its scoped neighbours
    agree: the producers of its operands and the users of its result,
    across at most ``_REACH`` more instructions like itself. Its phase is
    the latest of its producers' (it runs after them), else the earliest of
    its users'. Neighbours of more than one node, or none, leave it
    ``unscoped``."""
    by_name = {ins.name: ins for ins in body}
    users = {}
    for ins in body:
        for name in ins.operands:
            users.setdefault(name, []).append(ins)

    def reached(ins, step):
        found, seen, front = [], {ins.name}, [ins]
        for _ in range(_REACH + 1):
            nxt = []
            for at in front:
                for nb in step(at):
                    if nb.name in seen:
                        continue
                    seen.add(nb.name)
                    if table[nb.name] is UNSCOPED:
                        nxt.append(nb)
                    else:
                        found.append(table[nb.name])
            front = nxt
        return found

    adopted = {}
    for ins in body:
        if table[ins.name] is not UNSCOPED or ins.opcode in _PLUMBING:
            continue
        before = reached(ins, lambda at: [by_name[n] for n in at.operands
                                          if n in by_name])
        after = reached(ins, lambda at: users.get(at.name, ()))
        if len({s.node for s in before + after}) != 1:
            continue
        phase = max(before, key=lambda s: _ORDER[s.phase]).phase if before \
            else min(after, key=lambda s: _ORDER[s.phase]).phase
        adopted[ins.name] = (before + after)[0]._replace(phase=phase,
                                                         mixed=False)
    table.update(adopted)
