"""The jitted step's operations by the program's own scopes.

A profile of the chip names an operation as the compiler did
(``fusion.413``); what it was in the program — which phase of the step,
which sublayer — lives only in the compiled module's metadata:
``compiled.as_text()`` gives every instruction an ``op_name`` such as
``jit(train_step)/transpose(jvp())/layers/while/body/closed_call/
checkpoint/rematted_computation/ffn/dot_general``. This module holds the
ONE vocabulary of ``jax.named_scope`` names the step opens (the model
files import the names they open), reads such a path
(:func:`classify`), and turns a compiled step into a table from
instruction name to phase and scopes (:func:`table`), which a reader joins
with a device trace's events by name (``benchmark/lib/scope_trace.py``,
``tools/step_scopes.py``). Host-only: strings in, a dictionary out.

The vocabulary (README "Tracing & trace analysis" says where each is
opened):

* sublayers, the partition of the step's work: ``embed``, ``attn``,
  ``ffn``, ``moe_ffn``, ``mamba_mixer``, ``loss_head`` /
  ``exit_head_loss``, ``optimizer``. An operation belongs to the LAST
  sublayer on its path (``ffn/moe_ffn/...`` is the experts');
* kernels inside them: ``flash_attention``, ``ssm_scan``;
* groups round them: ``layers`` (the stack), ``loop_pass`` (one pass of a
  looped stack). An operation under a group and no sublayer is the layer
  scan's own plumbing: slices of stacked weights, writes of stacked
  gradients, the compiler's copies (``layer_scan``).

Phases: ``fwd`` (``jvp(``), ``bwd`` (``transpose(``), ``remat`` (the
forward recomputed inside the backward sweep: ``rematted_computation``
anywhere on the path), ``update`` (the ``optimizer`` scope), ``""``.
"""

import json
import re
import time
from collections import Counter, namedtuple
from pathlib import Path

EMBED, ATTN, FFN, MOE_FFN = "embed", "attn", "ffn", "moe_ffn"
MAMBA_MIXER, LOSS_HEAD, OPTIMIZER = "mamba_mixer", "loss_head", "optimizer"
EXIT_HEAD_LOSS = "exit_head_loss"
FLASH_ATTENTION, SSM_SCAN = "flash_attention", "ssm_scan"
LAYERS, LOOP_PASS = "layers", "loop_pass"

SUBLAYERS = (EMBED, ATTN, FFN, MOE_FFN, MAMBA_MIXER, LOSS_HEAD,
             EXIT_HEAD_LOSS, OPTIMIZER)
KERNELS = (FLASH_ATTENTION, SSM_SCAN)
GROUPS = (LAYERS, LOOP_PASS)
SCOPES = SUBLAYERS + KERNELS + GROUPS
LAYER_SCAN = "layer_scan"  # a group and no sublayer: no scope's name

FILE_NAME = "step_scopes.json"

_JIT_NAME = re.compile(r"\bp?jit\([^()]*\)")  # a function's name, no scope
_SEPARATORS = re.compile(r"[/()]")


def classify(op_name):  # jaxlint: host-only
    """``(phase, scopes)`` of an instruction's ``op_name``: the phase of
    the step it runs in and the program's own scope names on its path, in
    order, each once. A scope opened outside a differentiated function
    lies inside the transform's parentheses (``transpose(jvp(layers))/
    while/...``), one opened inside it after them; both count. Where the
    compiler merged instructions (``a;b``) the first part names the
    result."""
    name = _JIT_NAME.sub("", op_name.split(";", 1)[0])
    scopes = tuple(dict.fromkeys(
        part for part in _SEPARATORS.split(name) if part in SCOPES))
    if "rematted_computation" in name:
        phase = "remat"
    elif "transpose(" in name:
        phase = "bwd"
    elif "jvp(" in name:
        phase = "fwd"
    elif OPTIMIZER in scopes:
        phase = "update"
    else:
        phase = ""
    return phase, scopes


def sublayer(scopes):  # jaxlint: host-only
    """The part of the step's partition a path of scopes lies in: its last
    sublayer, ``layer_scan`` under a group alone, ``""`` under nothing."""
    inner = [s for s in scopes if s in SUBLAYERS]
    if inner:
        return inner[-1]
    return LAYER_SCAN if any(s in GROUPS for s in scopes) else ""


# ---- the compiled module's text ---------------------------------------------

_COMPUTATION = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(ROOT\s+)?%?([\w.\-]+)\s+=\s+(.*)$")
_OPCODE = re.compile(r"(?<![\w\-%.])([a-z][a-z0-9\-]*)\(")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
_REF = re.compile(r"%([\w.\-]+)")  # an operand (or a computation) by name
# the computations an instruction runs as instructions of their own (a
# reduction's ``to_apply`` is a scalar function inside one operation)
_RUNS = re.compile(
    r"\b(?:body|condition|true_computation|false_computation)=%?([\w.\-]+)"
    r"|\bbranch_computations=\{([^}]*)\}")
_TO_APPLY = re.compile(r"\bto_apply=%?([\w.\-]+)")
_PRODUCTS = ("convolution", "dot")
# never an event of a device trace: no work of their own
_NO_WORK = frozenset((
    "parameter", "constant", "get-tuple-element", "tuple", "bitcast",
    "after-all", "partition-id", "replica-id", "opt-barrier",
))


# one instruction of the module's text; ``rest``: what follows `` = ``
_Ins = namedtuple("_Ins", "name opcode op_name rest is_root")


def _parse(text):
    """``({computation: [_Ins]}, entry computation's name)`` of a module's
    text."""
    computations, entry, body = {}, None, None
    for line in text.splitlines():
        if body is None:
            m = _COMPUTATION.match(line)
            if m:
                body = computations.setdefault(m.group(2), [])
                if m.group(1):
                    entry = m.group(2)
            continue
        if line.startswith("}"):
            body = None
            continue
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        rest = m.group(3)
        op = _OPCODE.search(rest)
        named = _OP_NAME.search(rest)
        body.append(_Ins(m.group(2), op.group(1) if op else "",
                         named.group(1) if named else "", rest,
                         bool(m.group(1))))
    return computations, entry


def _fusion_facts(body):
    """``(op_name, root opcode, product)`` of a fused computation: the
    name of the product inside it where it holds one, else of its root;
    the opcode at its root (of a tuple's first member), and the product's
    opcode or ``""``."""
    root = next((i for i in body if i.is_root), body[-1])
    root_opcode, root_name = root.opcode, root.op_name
    if root_opcode == "tuple":
        first = re.search(r"tuple\(.*?%([\w.\-]+)", root.rest)
        member = first and next(
            (i for i in body if i.name == first.group(1)), None)
        if member:
            root_opcode = member.opcode
            root_name = root_name or member.op_name
    products = [i for i in body if i.opcode in _PRODUCTS]
    named = next((i for i in products if i.op_name), None)
    if named:
        return named.op_name, root_opcode, named.opcode
    return root_name, root_opcode, products[0].opcode if products else ""


def _named_by_neighbours(rows):
    """``{name: op_name}`` for the instructions of one computation
    (``rows``: ``(name, op_name, rest)``) the compiler gave no ``op_name``
    (its own copies, prefetches, broadcast constants): that of the nearest
    instruction that reads the result, through others without one;
    failing that, of the nearest that made an operand."""
    own = {name: op_name for name, op_name, _ in rows}
    if all(own.values()):
        return {}
    operands = {
        name: [o for o in _REF.findall(rest) if o in own]
        for name, _, rest in rows}
    users = {name: [] for name in own}
    for name, ops in operands.items():
        for o in ops:
            users[o].append(name)

    def nearest(start, edges):
        seen, frontier = {start}, [start]
        while frontier:
            reached = []
            for at in frontier:
                for nxt in edges[at]:
                    if own[nxt]:
                        return own[nxt]
                    if nxt not in seen:
                        seen.add(nxt)
                        reached.append(nxt)
            frontier = reached
        return ""

    return {name: nearest(name, users) or nearest(name, operands)
            for name, op_name in own.items() if not op_name}


def table(compiled):  # jaxlint: host-only
    """``{"module", "vocabulary", "instructions": {name: [phase, scopes,
    root opcode, product]}}`` over the instructions of the compiled
    module's non-fused computations that do work of their own, ``scopes``
    joined by ``/``. ``compiled``: a ``jax.stages.Compiled`` or its text.

    A fusion stands for its body and is read by the product inside it
    (``convolution`` / ``dot``: fourth field) where it holds one, else by
    its root, whose opcode is the third field: a weight-gradient product
    fused with its write into the stacked gradient reads ``[bwd,
    layers/ffn, dynamic-update-slice, convolution]``, a bare write
    ``[bwd, layers, dynamic-update-slice, ""]``. An instruction the
    compiler gave no ``op_name`` (its own copies and prefetches) takes
    that of the nearest instruction that reads its result, else of the
    one that made its operand, else of the instruction whose computation
    it runs in (the ``while`` of a layer scan)."""
    text = compiled if isinstance(compiled, str) else compiled.as_text()
    computations, entry = _parse(text)
    module = re.match(r"HloModule\s+([\w.\-]+)", text)
    instructions = {}
    pending, seen = [(entry, "")], set()
    while pending:
        comp, inherited = pending.pop()
        if comp in seen or comp not in computations:
            continue
        seen.add(comp)
        rows = []
        for name, opcode, op_name, rest, _ in computations[comp]:
            root_opcode, product = opcode, ""
            called = _CALLS.search(rest) if opcode == "fusion" else None
            body = computations.get(called.group(1)) if called else None
            if body:
                inner, root_opcode, product = _fusion_facts(body)
                op_name = (inner or op_name
                           or next((i.op_name for i in body if i.op_name), ""))
            rows.append((name, opcode, op_name, rest, root_opcode, product))
        borrowed = _named_by_neighbours(
            [(name, op_name, rest) for name, _, op_name, rest, _, _ in rows])
        for name, opcode, op_name, rest, root_opcode, product in rows:
            op_name = op_name or borrowed.get(name) or inherited
            runs = [a or b for a, b in _RUNS.findall(rest)]
            if opcode == "call":
                runs += _TO_APPLY.findall(rest)
            for group in runs:
                for target in re.findall(r"[\w.\-]+", group):
                    pending.append((target, op_name))
            if opcode in _NO_WORK:
                continue
            phase, scopes = classify(op_name)
            instructions[name] = [
                phase, "/".join(scopes), root_opcode, product]
    return {
        "module": module.group(1) if module else "",
        "vocabulary": {"sublayers": list(SUBLAYERS),
                       "kernels": list(KERNELS), "groups": list(GROUPS)},
        "instructions": instructions,
    }


def summary(tab):  # jaxlint: host-only
    """The counts the ``step_scopes`` event carries (never the table: it is
    tens of kilobytes, and the flight ring and the text log see every
    event): instructions by phase and by part of the partition, and how
    many have neither."""
    rows = tab["instructions"].values()
    parts = [(phase, sublayer(scopes.split("/"))) for phase, scopes, *_ in rows]
    return {
        "module": tab["module"],
        "instructions": len(parts),
        "by_phase": dict(Counter(p or "none" for p, _ in parts)),
        "by_scope": dict(Counter(s or "none" for _, s in parts)),
        "unscoped": sum(1 for p, s in parts if not p and not s),
    }


# (advisory: a profile reader's aid, rewritten by every run once its step
# has compiled)
def write(compiled, path):  # jaxlint: host-only  # faultcheck: tear-ok
    """Build the table of ``compiled``, write it to ``path`` and emit the
    one ``step_scopes`` event that names the file. Returns the event's
    fields."""
    from pyrecover_tpu import telemetry

    t0 = time.monotonic()
    tab = table(compiled)
    path = Path(path)
    path.parent.mkdir(parents=True, exist_ok=True)
    tmp = path.with_name(path.name + ".tmp")
    tmp.write_text(json.dumps(tab, separators=(",", ":")))
    tmp.replace(path)
    fields = dict(
        summary(tab), path=str(path),
        build_s=round(time.monotonic() - t0, 4))
    telemetry.emit("step_scopes", **fields)
    return fields
