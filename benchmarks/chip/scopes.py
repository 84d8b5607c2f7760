"""Device time by the program's named scopes.

The program names its sub-layers (``repro.models.scopes.SCOPES``) with
``jax.named_scope``; a scope reaches the compiled HLO as the ``op_name``
metadata of each op (and a ``scope`` frontend attribute, there only to
key JAX's persistent cache).  ``scope_map`` reads a compiled
program's text and gives each op the innermost scope on its ``op_name``
path; ``scope_time`` splits the trace's ops on one device, inside the runs
of that program, by those scopes.  Ops with no scope (those XLA inserts,
such as the copies of a loop's carried state) map to ``unscoped``.

A program older than the scopes has no ``repro.models.scopes``:
``scope_maps`` then gives ``None`` and every reader built on it reports
nothing.
"""
from __future__ import annotations

import bisect
import re
from typing import Dict, Iterable, Iterator, List, Optional, Set, Tuple

from benchmarks.chip import xplane

UNSCOPED = "unscoped"
TOP_OPS = 4            # ops listed per scope in a breakdown

COMPUTATION = re.compile(r"^(?:ENTRY\s+)?(%\S+)\s.*\{\s*$")
INSTRUCTION = re.compile(r"^\s+(ROOT\s+)?(%\S+)\s+=\s")
OP_NAME = re.compile(r'metadata=\{[^{}]*?op_name="((?:[^"\\]|\\.)*)"')
CALLS = re.compile(r"\bcalls=(%[^\s,}]+)")
NAME = re.compile(r"%[\w.\-]+")
METADATA = re.compile(r',?\s*metadata=\{(?:[^{}"]|"(?:[^"\\]|\\.)*")*\}')
SCOPE_ATTR = re.compile(r',scope="[^"]*"|(?<=\{)scope="[^"]*",?')
EMPTY_ATTRS = re.compile(r",?\s*frontend_attributes=\{\}")


def innermost(op_name: str, names: Set[str]) -> Optional[str]:
    """The last component of an ``op_name`` path that is a scope name;
    ``jit(...)``, ``while``, ``body``, ``closed_call``, ``shard_map`` and
    the primitive's own name are not."""
    for part in reversed(op_name.split("/")):
        if part in names:
            return part
    return None


def scope_map(compiled, names: Iterable[str]) -> Dict[str, str]:
    """``{"%fusion.202": "moe.experts", ...}`` for every instruction of a
    compiled program (an object with ``as_text()``, or its text).  A fusion
    whose own ``op_name`` holds no scope takes its fused computation's
    root's; a fused op that XLA made (a tuple, bitcast or convert without
    metadata) takes the first scope among its operands.  Anything else
    without a scope maps to ``unscoped``."""
    text = compiled if isinstance(compiled, str) else compiled.as_text()
    names = set(names)
    own: Dict[str, Optional[str]] = {}
    operands: Dict[str, List[str]] = {}
    calls: Dict[str, str] = {}
    roots: Dict[str, str] = {}
    comp = None
    for line in text.splitlines():
        m = INSTRUCTION.match(line)
        if m is None:
            c = COMPUTATION.match(line)
            if c is not None:
                comp = c.group(1)
            continue
        op = m.group(2)
        n = OP_NAME.search(line)
        own[op] = innermost(n.group(1), names) if n else None
        k = CALLS.search(line)
        if k is not None:
            calls[op] = k.group(1)
        rhs = line[m.end():]
        operands[op] = [t for t in NAME.findall(rhs) if t != calls.get(op)]
        if m.group(1) and comp is not None:
            roots[comp] = op

    memo: Dict[str, Optional[str]] = {}

    def fused(op: str) -> Optional[str]:
        """Scope of an op inside a fused computation: its own, its nested
        fusion's root's, else its operands' (depth first)."""
        if op not in memo:
            memo[op] = None                  # guards against a cycle
            got = own.get(op)
            if got is None and op in calls:
                got = fused(roots.get(calls[op], ""))
            for x in operands.get(op, ()) if got is None else ():
                got = fused(x)
                if got is not None:
                    break
            memo[op] = got
        return memo[op]

    def top(op: str) -> str:
        got = own.get(op)
        if got is None and op in calls:
            got = fused(roots.get(calls[op], ""))
        return got or UNSCOPED

    return {op: top(op) for op in own}


def strip_metadata(text: str) -> str:
    """A compiled program's text without what only names it: the module
    header, the source-location tables, every ``metadata={...}`` and the
    ``scope`` frontend attribute.  Two programs that differ only in their
    scopes strip to the same text, up to instruction numbering."""
    body = EMPTY_ATTRS.sub("", SCOPE_ATTR.sub("", METADATA.sub("", text)))
    lines = body.splitlines()
    start = next((i for i, ln in enumerate(lines)
                  if COMPUTATION.match(ln)), len(lines))
    return "\n".join(lines[start:]) + "\n"


def op_key(name: str) -> str:
    """The HLO name an op event carries (``%fusion.202 fusion bf16[..]``
    or ``fusion.202`` -> ``%fusion.202``)."""
    head = name.split(" ", 1)[0]
    return head if head.startswith("%") else "%" + head


def run_intervals(tr: xplane.Trace, dev: int, module: str, lo: float,
                  hi: float) -> List[xplane.Interval]:
    """The runs of one program on ``dev`` that overlap the window, matched
    by module name as ``xplane.module_runs`` matches them."""
    return sorted((s, e) for n, s, e in tr.modules.get(dev, [])
                  if e > lo and s < hi
                  and (n == module or n.startswith(module + "(")))


def ops_in_runs(tr: xplane.Trace, dev: int, runs: List[xplane.Interval],
                collectives: bool = False) -> Iterator[xplane.Event]:
    """The ops on ``dev`` that start inside one of ``runs`` (sorted),
    clipped to it; with ``collectives``, only the collective ops."""
    starts = [s for s, _ in runs]
    for name, s, e in tr.ops.get(dev, []):
        if collectives and not xplane.is_collective(name):
            continue
        i = bisect.bisect_right(starts, s) - 1
        if i < 0:
            continue
        s, e = max(s, runs[i][0]), min(e, runs[i][1])
        if e > s:
            yield name, s, e


def scope_intervals(tr: xplane.Trace, dev: int, module: str,
                    smap: Dict[str, str], lo: float, hi: float,
                    collectives: bool = False
                    ) -> Tuple[List[xplane.Interval],
                               Dict[str, List[xplane.Interval]]]:
    """The program's runs, and per scope the union of its ops' intervals
    inside those runs (ops outside every run are dropped)."""
    runs = run_intervals(tr, dev, module, lo, hi)
    by: Dict[str, List[xplane.Interval]] = {}
    for name, s, e in ops_in_runs(tr, dev, runs, collectives):
        by.setdefault(smap.get(op_key(name), UNSCOPED), []).append((s, e))
    return runs, {k: xplane.union(v) for k, v in by.items()}


def scope_time(tr: xplane.Trace, dev: int, module: str,
               smap: Dict[str, str], lo: float, hi: float,
               collectives: bool = False) -> Dict[str, float]:
    """Device seconds per scope inside the runs of ``module`` on ``dev``
    that overlap ``[lo, hi]``: the union of each scope's op intervals."""
    _, by = scope_intervals(tr, dev, module, smap, lo, hi, collectives)
    return {k: xplane.total(v) for k, v in by.items()}


def breakdown(tr: xplane.Trace, dev: int, lo: float, hi: float,
              programs: Dict[str, str], maps: Dict[str, Dict[str, str]]
              ) -> Dict[str, dict]:
    """Per program (``records["programs"]``, key -> module): its runs that
    overlap the window, device ms per run, the idle ms per run inside
    them, and ms per run by scope (``unscoped`` included, largest first)
    with each scope's ``TOP_OPS`` longest ops."""
    out = {}
    for key, module in programs.items():
        smap = maps.get(module, {})
        runs, by = scope_intervals(tr, dev, module, smap, lo, hi)
        if not runs:
            continue
        n = len(runs)
        busy = xplane.total(xplane.union(iv for v in by.values() for iv in v))
        ops: Dict[str, Dict[str, float]] = {}
        for name, s, e in ops_in_runs(tr, dev, runs):
            got = ops.setdefault(smap.get(op_key(name), UNSCOPED), {})
            got[name] = got.get(name, 0.0) + (e - s)
        scopes = sorted(((k, 1e3 * xplane.total(v) / n)
                         for k, v in by.items()), key=lambda kv: -kv[1])
        out[key] = {
            "module": module, "runs": n,
            "run_ms": 1e3 * xplane.total(runs) / n,
            "idle_ms": 1e3 * (xplane.total(runs) - busy) / n,
            "scopes": [[k, v] for k, v in scopes],
            "top_ops": {k: [[o, 1e3 * t / n] for o, t in sorted(
                v.items(), key=lambda kv: -kv[1])[:TOP_OPS]]
                for k, v in ops.items()}}
    return out


# ------------------------------------------------------------- readers
def live_texts(modules: Iterable[str]) -> Dict[str, str]:
    """The compiled text of each live executable whose HLO module has one
    of these names (the harness reads traces while the driver still holds
    its compiled programs)."""
    import jax

    wanted, out = set(modules), {}
    for exe in jax.devices()[0].client.live_executables():
        for mod in exe.hlo_modules():
            if mod.name in wanted:
                out[mod.name] = mod.to_string()
    return out


def scope_maps(records: dict) -> Optional[Dict[str, Dict[str, str]]]:
    """``{module: scope_map}`` for the programs in ``records["programs"]``,
    kept in ``records["scopes"]`` (a driver may have put it there).  None
    if the program defines no scopes."""
    try:
        from repro.models.scopes import SCOPES
    except ImportError:
        return None
    if "scopes" not in records:
        texts = live_texts(records.get("programs", {}).values())
        records["scopes"] = {m: scope_map(t, SCOPES)
                             for m, t in texts.items()}
    return records["scopes"]


def per_run_ms(ctx: dict, program: str, names: Tuple[str, ...],
               collectives: bool = False) -> Optional[float]:
    """Mean device milliseconds per run of ``records["programs"][program]``
    on the first chip in the union of the ops of the scopes ``names``
    (with ``collectives``, of their collective ops).  None where the
    program has no scopes, did not run, or ran no op of a named scope
    other than ``unscoped``."""
    rec = ctx["records"]
    module = rec.get("programs", {}).get(program)
    maps = scope_maps(rec) if module else None
    if not maps or module not in maps:
        return None
    lo, hi = ctx["window"]
    runs, by = scope_intervals(ctx["trace"], ctx["device"], module,
                               maps[module], lo, hi, collectives)
    if not runs or not any(n in by for n in names if n != UNSCOPED):
        return None
    got = xplane.union(iv for n in names for iv in by.get(n, ()))
    return 1e3 * xplane.total(got) / len(runs)
