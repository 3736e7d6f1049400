"""Device time by the program's named scopes.

The program names the sweep's work with ``jax.named_scope``: the parent
``sweep`` and, inside it, ``rng`` (the random draw), ``layout`` (moves
between layouts), ``halo`` (halo lines: rolls or ``ppermute`` and their
adds), ``flip`` (acceptance) and ``measure`` (the streamed (m, E)). A scope
reaches the compiled HLO as the ``op_name`` metadata of each instruction,
``jit(f)/while/body/.../sweep/rng/...``, and an op is charged to the
innermost of these names in its path (:func:`innermost`).

A trace's ``XLA Ops`` events carry an instruction's text but not its
metadata, so the map from instruction to scope comes from the compiled text
of the chunk program (:func:`scope_map`). There an op is charged as
follows. A fusion goes to the scope of its fused computation's root, as on
the CPU, where a fusion carries its root's ``op_name``. The TPU compiler
gives a fusion no metadata, or that of an op it made itself, and the root
can be such an op too (the convert that ends the random draw, a tuple):
then the fusion goes to the scope that most of its fused instructions
carry. Any other op goes to the scope of its own ``op_name``. A copy
that XLA added, with no scope in its ``op_name`` (or none, or one the
partitioner made, ``shard_map/reshape.1138``), moves bytes between layouts
or buffers and goes to ``layout``; any other op without an ``op_name`` goes
to the scope of its first operand that is charged to one.

In a ``--trace 1`` run the map is built from the chunk program that
``IsingEngine.lower`` gives for the cell, compiled again in the same process
(:func:`program_scopes`). The compile is deterministic, so the op names are
those of the executable that ran; the program keeps op names in its
compilation cache's key, so that executable was compiled from the same
module, scopes included.
A program without the scopes, or without ``lower`` for the cell's scenario,
gives no map, and the metrics that read it are silent.
"""
from __future__ import annotations

import re
from collections import Counter

SCOPES = ("sweep", "rng", "layout", "halo", "flip", "measure")
UNSCOPED = ""

_HEADER = re.compile(r"^(?:ENTRY\s+)?%(\S+)\s.*\{\s*$")
_INSTR = re.compile(r"^\s+(ROOT\s+)?%(\S+) = (.*)$")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_CALLS = re.compile(r"\bcalls=%([\w.\-]+)")
_REF = re.compile(r"%([\w.\-]+)")
_OPCODE = re.compile(r" ([a-z][a-z0-9-]*)\(")
COPIES = ("copy", "copy-start", "copy-done")

_programs: dict = {}


def innermost(op_name: str) -> str:
    """The innermost scope of :data:`SCOPES` in an ``op_name`` path (the
    first path where XLA joined several with ``;``)."""
    for part in reversed(op_name.split(";")[0].split("/")):
        if part in SCOPES:
            return part
    return UNSCOPED


def _parse(hlo_text: str) -> tuple:
    """Per instruction: (computation, op_name or None, callee, operands,
    opcode); and the root of each computation."""
    instrs, roots, comp = {}, {}, None
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m and comp is not None:
            is_root, name, rest = m.groups()
            op = _OP_NAME.search(rest)
            calls = _CALLS.search(rest)
            body = rest.split(", metadata=", 1)[0]
            opcode = _OPCODE.search(" " + body)
            instrs[name] = (comp, op.group(1) if op else None,
                            calls.group(1) if calls else None,
                            _REF.findall(body),
                            opcode.group(1) if opcode else "")
            if is_root:
                roots[comp] = name
            continue
        h = _HEADER.match(line)
        if h:
            comp = h.group(1)
    return instrs, roots


def scope_map(hlo_text: str) -> dict:
    """{instruction name: innermost scope or ""} of a compiled HLO module."""
    instrs, roots = _parse(hlo_text)
    votes: dict = {}
    for comp, op_name, *_ in instrs.values():
        if op_name is not None and innermost(op_name):
            votes.setdefault(comp, Counter())[innermost(op_name)] += 1
    memo: dict = {}

    def scope(name: str, depth: int = 0):
        if name in memo or depth > 64:
            return memo.get(name)
        memo[name] = None                   # cycle guard
        comp, op_name, calls, refs, opcode = instrs[name]
        root_op = instrs[roots[calls]][1] if calls in roots else None
        if root_op is not None and innermost(root_op):
            found = innermost(root_op)      # a fusion: its root's scope
        elif calls in votes:                # or that of most of its ops
            found = votes[calls].most_common(1)[0][0]
        elif op_name is not None and innermost(op_name):
            found = innermost(op_name)
        elif opcode in COPIES:
            found = "layout"
        elif op_name is not None:
            found = UNSCOPED
        else:       # the first operand charged to a scope, else unscoped
            found = None
            for ref in refs:
                if ref == name or instrs.get(ref, (None,))[0] != comp:
                    continue
                got = scope(ref, depth + 1)
                if got:
                    found = got
                    break
                if got is not None:
                    found = UNSCOPED
        memo[name] = found
        return found

    return {name: scope(name) or UNSCOPED for name in instrs}


def program_scopes(cell) -> dict | None:
    """The scope map of ``cell``'s chunk program, compiled for this
    process's TPU; None off a TPU, or where the program names no scope."""
    if cell.name not in _programs:
        _programs[cell.name] = _compile_scopes(cell)
    return _programs[cell.name]


def _compile_scopes(cell) -> dict | None:
    import jax
    import jax.numpy as jnp

    if jax.default_backend() != "tpu":
        return None
    import run

    engine = run.make_engine(cell, {})
    if not hasattr(engine, "lower"):
        return None
    template = engine.state_template()
    state = jax.ShapeDtypeStruct(template.shape, template.dtype,
                                 sharding=engine.state_sharding())
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    try:
        lowered = engine.lower(state, key)
    except ValueError:      # a scenario whose chunk program it cannot give
        return None
    found = scope_map(_compile_anew(lowered))
    return found if any(found.values()) else None


def _compile_anew(lowered) -> str:
    """Compiled text of ``lowered`` from a compile that skips the persistent
    cache: an executable read back from it may carry the op names of
    another program that compiled to the same code."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return lowered.compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def shares(trace, scope_of: dict) -> dict:
    """{scope: % of device busy time} of the ops charged to each scope
    (``""`` for the rest), mean over devices. An op's time is its duration
    clipped to the window; ops of one device do not overlap, so the shares
    sum to 100."""
    per_dev = []
    for dev in trace.devices():
        busy = trace.busy_ns(dev)
        if busy <= 0:
            continue
        total = dict.fromkeys(SCOPES + (UNSCOPED,), 0.0)
        for name, _, s, e in trace.ops[dev]:
            s, e = max(s, trace.start), min(e, trace.end)
            if s < e:
                total[scope_of.get(name, UNSCOPED)] += e - s
        per_dev.append({k: 100.0 * v / busy for k, v in total.items()})
    if not per_dev:
        return {}
    return {k: sum(d[k] for d in per_dev) / len(per_dev) for k in per_dev[0]}


def share(ctx, scope: str):
    """The metric readers' entry: ``scope``'s share in the traced chunks,
    or None where the program gives no scope map."""
    scope_of = program_scopes(ctx.cell)
    if scope_of is None:
        return None
    return shares(ctx.trace, scope_of).get(scope)
