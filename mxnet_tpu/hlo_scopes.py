"""Which scope of the program each instruction of a compiled module lies
under: the join from a device trace's events (named by instruction) back to
the Gluon blocks and ``mx_*`` scopes that wrote them.

``jax.named_scope`` names reach a compiled module as
``metadata={op_name="jit(step)/transpose(jvp(Model.m_))/jvp(Model.m_)/
checkpoint/rematted_computation/Layer.l3_/Attn.attn_/mx_front/dot_general"}``
and nowhere else; ``scope_map_of`` reads them out of the module's text
(``compiled.as_text()``) once, by these rules:

* **scope**: the path's elements that are a block (``Class.instance``,
  ``gluon/block.py`` ``trace_scope``) or an ``mx_*`` scope, jax's wrappers
  (``jvp(..)``, ``transpose(..)``) taken off, an element said twice in a row
  once: ``Model.m_/Layer.l3_/Attn.attn_/mx_front``.  ``block`` is its
  innermost ``Class.instance``.
* **dir**: ``remat`` under jax's ``rematted_computation`` (a recomputed
  layer's second forward; jax nests it INSIDE the ``transpose(``, so it is
  asked first), else ``bwd`` under a ``transpose(``, else ``fwd``.
* a **fusion** takes its ROOT's scope (of a tuple root the first element's;
  where the root carries none, the fusion instruction's own), and ``mixed``
  is the scope of a ``dot`` or ``convolution`` inside it that lies under
  another block: the weight gradient XLA fused into Adam has scope
  ``mx_update`` and ``mixed`` the layer whose weight it is.
* an instruction that carries NO ``op_name`` (or an argument's name alone,
  ``params['w']``) is XLA's own (a relayout copy, a
  fill of zeros, an async copy's two halves, a loop it made of a scatter) and
  takes a neighbour's scope and direction: a ``while``, ``call`` or
  ``conditional`` its body's first scoped instruction's, anything else its
  first scoped operand's, else its first scoped user's (zeros belong to
  what they are filled for).  An instruction the program wrote outside
  every scope has an ``op_name`` and stays unscoped.
* **entry** says the instruction is in the ENTRY computation.  A ``while``,
  ``call`` or ``conditional`` there is ONE event that spans its body, and
  the body's instructions are events too: a reader sums ``entry``
  instructions only and counts nothing twice.

Instructions of fused computations and of reducers are never events and are
left out.  Pure text in, a dict out: nothing here touches jax.
"""
import re

__all__ = ["scope_map_of", "scope_of"]

_INSTR = re.compile(r"^\s+(ROOT )?%([^ ]+) = .+? ([a-z][\w\-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLED = re.compile(r"\b(calls|body|condition|to_apply)=%([\w.\-]+)")
_OPERAND = re.compile(r"%([\w.\-]+)")
_WRAPPED = re.compile(r"^\w+\((.*)\)$")
_BLOCK = re.compile(r"^[A-Za-z_]\w*\.\w*$")
_HAND = re.compile(r"^mx_\w+$")
_PRODUCTS = ("dot", "convolution")


def scope_of(op_name):
    """One ``op_name`` -> (scope path, innermost block, direction)."""
    parts = op_name.split(";")[0].split("/")
    if "rematted_computation" in parts:
        direction = "remat"
    elif any(p.startswith("transpose(") for p in parts):
        direction = "bwd"
    else:
        direction = "fwd"
    path = []
    for p in parts:
        m = _WRAPPED.match(p)
        while m:
            p = m.group(1)
            m = _WRAPPED.match(p)
        if (_BLOCK.match(p) or _HAND.match(p)) and path[-1:] != [p]:
            path.append(p)
    block = next((p for p in reversed(path) if _BLOCK.match(p)), "")
    return "/".join(path), block, direction


def _blocks(scope):
    return [p for p in scope.split("/") if _BLOCK.match(p)]


def _parse(text):
    """The module's text -> ({computation: [(name, opcode, op_name, root,
    line)]}, the ENTRY computation's name)."""
    comps, entry, cur = {}, None, None
    for line in text.splitlines():
        if cur is None:
            if line.endswith("{") and line.startswith(("%", "ENTRY ")):
                name = line.split(" (", 1)[0].split()[-1].lstrip("%")
                cur = comps.setdefault(name, [])
                if line.startswith("ENTRY "):
                    entry = name
            continue
        if line.startswith("}"):
            cur = None
            continue
        m = _INSTR.match(line)
        if m:
            op = _OP_NAME.search(line)
            cur.append((m.group(2), m.group(3), op.group(1) if op else "",
                        bool(m.group(1)), line))
    return comps, entry


def _fusion_scope(body, own):
    """(op_name a fusion is placed by, the ``mixed`` scope or None) from its
    fused computation's instructions."""
    by_name = {name: (opcode, op, line) for name, opcode, op, _r, line in body}
    placed = ""
    roots = [row for row in body if row[3]]
    if roots:
        _name, opcode, placed, _r, line = roots[-1]
        if opcode == "tuple":
            first = _OPERAND.search(line[line.index(" tuple("):])
            placed = by_name.get(first.group(1) if first else "",
                                 ("", "", ""))[1]
    placed = placed or own
    if not placed:      # XLA's own root (a copy, a bitcast): the nearest op
        placed = next((op for _n, _o, op, _r, _l in reversed(body) if op), "")
    home = _blocks(scope_of(placed)[0])
    mixed = None
    for _name, opcode, op, _r, _line in body:
        if opcode in _PRODUCTS and op:
            scope = scope_of(op)[0]
            if _blocks(scope) != home:
                mixed = scope
                break
    return placed, mixed


def _inherit(body, rows, comps):
    """XLA's own instructions of one computation take a neighbour's scope
    (the module docstring's rule), in place."""
    def scoped(name):
        return name in rows and rows[name]["scope"]

    def take(name, source):
        rows[name].update(scope=source["scope"], block=source["block"],
                          dir=source["dir"])

    bare, users = [], {}
    for name, opcode, op, _root, line in body:
        refs = [r for r in _OPERAND.findall(line.split(" = ", 1)[1])
                if r in rows and r != name]
        for ref in refs:
            users.setdefault(ref, []).append(name)
        if "/" in op or rows[name]["scope"]:    # an argument's name is none
            continue
        bare.append(name)
        source = None
        if opcode in ("while", "call", "conditional"):
            called = [t for _k, t in _CALLED.findall(line) if t in comps]
            source = next((scope_of(o) for t in called
                           for _n, _o, o, _r, _l in comps[t]
                           if o and scope_of(o)[0]), None)
            if source:
                source = dict(zip(("scope", "block", "dir"), source))
        else:       # operands come first in the text: already placed
            source = next((rows[r] for r in refs if scoped(r)), None)
        if source:
            take(name, source)
    for name in reversed(bare):
        if not rows[name]["scope"]:
            source = next((rows[u] for u in users.get(name, ())
                           if scoped(u)), None)
            if source:
                take(name, source)


def scope_map_of(text):
    """A compiled module's text -> {instruction name: {"scope", "block",
    "dir", "entry", "mixed"}} for every instruction that can be a device
    event (the module docstring has the rules)."""
    comps, entry = _parse(text)
    never = set()       # fused computations and reducers: no events inside
    for body in comps.values():
        for _name, opcode, _op, _root, line in body:
            for key, target in _CALLED.findall(line):
                if key == "calls" or (key == "to_apply" and opcode != "call"):
                    never.add(target)
    out = {}
    for comp, body in comps.items():
        if comp in never:
            continue
        rows, placed = {}, []
        for name, opcode, op, root, line in body:
            mixed = None
            if opcode == "fusion":
                called = dict(_CALLED.findall(line)).get("calls")
                if called in comps:
                    op, mixed = _fusion_scope(comps[called], op)
            scope, block, direction = scope_of(op)
            rows[name] = {"scope": scope, "block": block, "dir": direction,
                          "entry": comp == entry, "mixed": mixed}
            placed.append((name, opcode, op, root, line))
        _inherit(placed, rows, comps)
        out.update(rows)
    return out
