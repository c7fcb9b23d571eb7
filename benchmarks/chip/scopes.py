"""Read the traced window by the program's own names.

The program (``repro.core.tap``) puts host spans on the profiler's
clock (``repro.rank``, ``repro.preprocess``, ``repro.plan``,
``repro.launch.<program>``, ``repro.fetch``, the entry points) and names
the phases of its jitted loops with ``jax.named_scope``
(``DEVICE_SCOPES``). The device trace names each operation by its HLO
instruction (``fusion.284``), a number a recompile changes, so this
module maps instructions to scopes through the compiled program:

- each ``(name, args, kwargs)`` of ``run.programs`` is resolved by name
  in the program's modules, lowered and compiled with the same
  arguments (``lower(*args, **kwargs).compile().as_text()``), and each
  instruction of the text gets the innermost ``DEVICE_SCOPES`` entry of
  its ``op_name``; an instruction the compiler made without one takes
  the scope of the computation it calls, else of the instructions its
  result feeds, else of those that feed it (:func:`instruction_scopes`);
- each device event of the window belongs to the program whose
  ``repro.launch.*`` span last started before it, up to the end of the
  first ``repro.fetch`` after that span, give or take
  ``CLOCK_SLACK_NS`` (two programs can both hold a ``fusion.12``);
  other events belong to no program;
- self time is ``trace._self_times`` over each program's events.

Every reader returns None where the program has no such span or scope
(a program without the tap), so a metric that reads it falls silent.
"""
from __future__ import annotations

import bisect
import collections
import importlib
import re
from typing import Optional

from benchmarks.chip import trace as trace_mod

SPAN = "repro."
LAUNCH = "repro.launch."
FETCH = "repro.fetch"
PREP_SPANS = ("repro.rank", "repro.preprocess", "repro.plan")
PROGRAM_MODULES = ("repro.core.pipeline", "repro.core.peel",
                   "repro.core.count")
KINDS = ("fusion", "custom-call", "scatter", "sort")  # the device work
# A program's first device operations were seen up to about 0.7 ms
# before its launch span starts (TPU v5e traces): the two timelines are
# aligned to within this much.
CLOCK_SLACK_NS = 1e6
# A program's operations follow each other within this much; eager
# operations the host dispatches one by one are farther apart.
CONTIGUOUS_NS = 50_000

_COMP = re.compile(r"^(?:ENTRY\s+)?%([\w.\-]+)\s.*\{\s*$")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_OPCODE = re.compile(r"\s*([\w\-]+)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_REF = re.compile(r"%([\w.\-]+)")


def device_scopes() -> Optional[tuple]:
    """The program's ``DEVICE_SCOPES``, or None where it has none."""
    try:
        return tuple(importlib.import_module("repro.core.pipeline")
                     .DEVICE_SCOPES)
    except (ImportError, AttributeError):
        return None


def _skip_type(rest: str) -> str:
    """What follows an instruction's result type (a tuple type is
    parenthesised and may nest)."""
    rest = rest.lstrip()
    if not rest.startswith("("):
        return rest.split(" ", 1)[1] if " " in rest else ""
    depth = 0
    for i, ch in enumerate(rest):
        depth += ch == "("
        depth -= ch == ")"
        if depth == 0:
            return rest[i + 1:]
    return ""


def _split_operands(body: str):
    """``(operand text, attribute text)`` of ``opcode(...), attrs``."""
    depth = 0
    for i, ch in enumerate(body):
        depth += ch == "("
        depth -= ch == ")"
        if depth == 0 and i:
            return body[1:i], body[i + 1:]
    return body, ""


def parse_hlo(text: str) -> dict:
    """``{computation: [instruction]}`` of an HLO module's text; each
    instruction a dict of ``name``, ``opcode``, ``op_name``,
    ``operands``, ``calls`` (computations it references) and ``root``."""
    comps, cur = {}, None
    for line in text.splitlines():
        if cur is None:  # a computation opens at column 0
            m = _COMP.match(line)
            if m:
                cur = comps.setdefault(m.group(1), [])
            continue
        if line.strip() == "}":
            cur = None
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        after = _skip_type(m.group(2))
        op = _OPCODE.match(after)
        if not op:
            continue
        operands, attrs = _split_operands(after[op.end() - 1:])
        name_m = _OP_NAME.search(attrs)
        cur.append({
            "name": m.group(1), "opcode": op.group(1),
            "op_name": name_m.group(1) if name_m else "",
            "operands": _REF.findall(operands),
            "calls": _REF.findall(_OP_NAME.sub("", attrs)),
            "root": line.lstrip().startswith("ROOT "),
        })
    return comps


def innermost(op_name: str, scopes) -> Optional[str]:
    """The innermost ``scopes`` entry among an ``op_name``'s parts."""
    for part in reversed(op_name.split("/")):
        if part in scopes:
            return part
    return None


def instruction_scopes(text: str, scopes) -> dict:
    """``{instruction: (scope or None, how)}`` for every instruction of
    a compiled module's text. ``how`` is ``"op_name"`` (its own
    metadata), ``"calls"`` (the root, else the most common scope, of the
    computations it calls), ``"users"`` or ``"operands"`` (the nearest
    scoped instruction its result feeds, else that feeds it, in its own
    computation), or ``None``."""
    comps = parse_hlo(text)
    own = {}
    for instrs in comps.values():
        for ins in instrs:
            own[ins["name"]] = innermost(ins["op_name"], scopes)

    memo: dict = {}

    def of_comp(comp: str, seen: frozenset) -> Optional[str]:
        if comp in memo:
            return memo[comp]
        found, root = collections.Counter(), None
        for ins in comps.get(comp, ()):
            s = own[ins["name"]] or of_calls(ins, seen | {comp})
            if s:
                found[s] += 1
                if ins["root"]:
                    root = s
        memo[comp] = root or (found.most_common(1)[0][0] if found else None)
        return memo[comp]

    def of_calls(ins, seen) -> Optional[str]:
        for c in ins["calls"]:
            if c in comps and c not in seen:
                s = of_comp(c, seen)
                if s:
                    return s
        return None

    out = {}
    for comp, instrs in comps.items():
        users = collections.defaultdict(list)
        by_name = {ins["name"]: ins for ins in instrs}
        for ins in instrs:
            for o in ins["operands"]:
                users[o].append(ins["name"])
        direct = {}
        for ins in instrs:
            s = own[ins["name"]]
            if s:
                direct[ins["name"]] = (s, "op_name")
            else:
                s = of_calls(ins, frozenset({comp}))
                if s:
                    direct[ins["name"]] = (s, "calls")

        def nearest(start, edges):
            todo, seen = collections.deque(edges(start)), {start}
            while todo:
                n = todo.popleft()
                if n in seen:
                    continue
                seen.add(n)
                if n in direct:
                    return direct[n][0]
                todo.extend(edges(n))
            return None

        for ins in instrs:
            n = ins["name"]
            if n in direct:
                out[n] = direct[n]
                continue
            s = nearest(n, lambda x: users.get(x, ()))
            if s:
                out[n] = (s, "users")
                continue
            s = nearest(n, lambda x: [o for o in by_name[x]["operands"]
                                      if o in by_name] if x in by_name
                        else ())
            out[n] = (s, "operands") if s else (None, None)
    return out


def coverage(text: str, scopes, kinds=KINDS) -> dict:
    """Counts of the instructions of ``kinds`` by how they got a scope."""
    kinds_of = {ins["name"]: ins["opcode"]
                for instrs in parse_hlo(text).values() for ins in instrs}
    out = collections.Counter()
    for n, (_s, how) in instruction_scopes(text, scopes).items():
        if kinds_of.get(n) in kinds:
            out[how] += 1
    return dict(out)


def resolve(name: str):
    """The jitted program ``name`` from the program's modules."""
    for mod in PROGRAM_MODULES:
        fn = getattr(importlib.import_module(mod), name, None)
        if fn is not None and hasattr(fn, "lower"):
            return fn
    raise LookupError(f"no jitted program {name!r} in {PROGRAM_MODULES}")


def program_scopes(run) -> Optional[dict]:
    """``{program: {instruction: (scope, how)}}`` of the programs the
    run recorded, compiled once per run; None without the tap."""
    cached = getattr(run, "_program_scopes", None)
    if cached is not None:
        return cached
    scopes = device_scopes()
    if scopes is None:
        return None
    out = {}
    for name, args, kwargs in run.programs:
        if name not in out:
            text = resolve(name).lower(*args, **kwargs).compile().as_text()
            out[name] = instruction_scopes(text, scopes)
    run._program_scopes = out
    return out


def _spans(run, prefix: str) -> list:
    """``[(name, start, end)]`` of the host spans named ``prefix*``, by
    start (an enclosing span first)."""
    return sorted(((n, s, s + d) for n, s, d in run.trace.host
                   if n.startswith(prefix)), key=lambda x: (x[1], -x[2]))


def _in_jobs(run, spans) -> list:
    return [sp for sp in spans
            if any(js <= sp[1] < je for js, je in run.trace.jobs)]


def _reaches(run, launches) -> list:
    """``(start, end, program)`` of each launch: its span's start to the
    end of the first ``repro.fetch`` after it (the window's end if
    none), by start."""
    fetch_ends = sorted(e for _n, _s, e in _spans(run, FETCH))
    out = []
    for n, s, _e in launches:
        i = bisect.bisect_right(fetch_ends, s)
        out.append((s, fetch_ends[i] if i < len(fetch_ends) else run.trace.w1,
                    n[len(LAUNCH):]))
    return out


def program_events(run) -> Optional[dict]:
    """``{program or None: [event]}``: the window's device events by the
    launch whose reach holds them, give or take ``CLOCK_SLACK_NS`` (None:
    no launch's reach); None without launch spans."""
    launches = _spans(run, LAUNCH)
    if not launches:
        return None
    reach = _reaches(run, launches)
    starts = [r[0] for r in reach]
    out = collections.defaultdict(list)
    for ev in run.trace._window(run.trace.device[0]):
        i = bisect.bisect_right(starts, ev[1] + CLOCK_SLACK_NS) - 1
        ok = i >= 0 and ev[1] <= reach[i][1] + CLOCK_SLACK_NS
        out[reach[i][2] if ok else None].append(ev)
    return dict(out)


def scope_self_ns(run) -> Optional[dict]:
    """``{(program, instruction): (scope, self ns)}`` over the window;
    None without the tap."""
    events = program_events(run)
    table = program_scopes(run) if events is not None else None
    if table is None:
        return None
    out = {}
    for prog, evs in events.items():
        for instr, ns in trace_mod._self_times(evs).items():
            scope = table.get(prog, {}).get(instr, (None, None))[0]
            out[(prog, instr)] = (scope, ns)
    return out


def busy_share(run, scope: str) -> Optional[float]:
    """Percent of the device's busy time in the self time of ``scope``."""
    table = scope_self_ns(run)
    busy = run.trace.busy_s
    if not table or not busy:
        return None
    ns = sum(v for s, v in table.values() if s == scope)
    return 100.0 * ns * 1e-9 / busy


def host_prep_s(run) -> Optional[float]:
    """Mean seconds a job of the outermost ``repro.rank``,
    ``repro.preprocess`` and ``repro.plan`` spans."""
    spans = _in_jobs(run, [sp for sp in _spans(run, SPAN)
                           if sp[0] in PREP_SPANS])
    if not spans:
        return None
    total, end = 0.0, float("-inf")
    for _n, s, e in spans:  # by start: skip spans inside the last counted
        if s >= end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total * 1e-9 / len(run.trace.jobs)


def host_syncs(run) -> Optional[float]:
    """``repro.fetch`` spans a job."""
    n = len(_in_jobs(run, _spans(run, FETCH)))
    return n / len(run.trace.jobs) if n else None


def _innermost_span(spans, t: float) -> str:
    best = None
    for n, s, e in spans:
        if s <= t < e and (best is None or s >= best[1]):
            best = (n, s)
    return best[0] if best else "no repro span"


def _idle(run) -> list:
    """Chip 0's idle intervals in the window."""
    gaps, t = [], run.trace.w0
    for s, e in run.trace._busy[0] if run.trace._busy else []:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if t < run.trace.w1:
        gaps.append((t, run.trace.w1))
    return gaps


def idle_by_span(run) -> dict:
    """``{span: idle seconds a job}``: chip 0's idle time split at span
    boundaries and given to the innermost ``repro.*`` span over it."""
    spans = _spans(run, SPAN)
    cuts = sorted({t for _n, s, e in spans for t in (s, e)})
    out = collections.Counter()
    for g0, g1 in _idle(run):
        pts = ([g0] + cuts[bisect.bisect_right(cuts, g0):
                           bisect.bisect_left(cuts, g1)] + [g1])
        for a, b in zip(pts, pts[1:]):
            out[_innermost_span(spans, (a + b) / 2)] += b - a
    jobs = len(run.trace.jobs)
    return {k: v * 1e-9 / jobs for k, v in out.most_common()}


def breakdown(run, top: int = 10) -> Optional[dict]:
    """For the tables of ``PERF.md``: device instructions by self time
    with their program and scope, self time by scope, the share of
    self time that maps to a scope and the largest instructions that do
    not, idle time by the innermost ``repro.*`` span and the longest
    idle gaps of chip 0 by the span through their middle, and how far a
    program's device events stray outside its launch's reach
    (:func:`clock_misses`)."""
    table = scope_self_ns(run)
    if table is None:
        return None
    total = sum(v for _s, v in table.values()) or 1.0
    by_scope = collections.Counter()
    for s, v in table.values():
        by_scope[s or "unscoped"] += v
    ops = sorted(table.items(), key=lambda kv: -kv[1][1])[:top]
    spans = _spans(run, SPAN)
    gaps = sorted(_idle(run), key=lambda g: g[0] - g[1])[:top]
    return {
        "device_ops": [[p, i, s, v * 1e-9] for (p, i), (s, v) in ops],
        "by_scope": {k: v * 1e-9 for k, v in by_scope.most_common()},
        "scoped_share": 100.0 * (total - by_scope.get("unscoped", 0.0))
        / total,
        "unscoped": sorted(([p, i, v * 1e-9] for (p, i), (s, v)
                            in table.items() if s is None),
                           key=lambda x: -x[2])[:top],
        "idle_by_span": idle_by_span(run),
        "idle_gaps": [[_innermost_span(spans, (s + e) / 2), (e - s) * 1e-9]
                      for s, e in gaps],
        "clock_misses": clock_misses(run),
    }


def clock_misses(run, top: int = 5) -> Optional[list]:
    """How far a launched program's device work strays outside its reach
    (its ``repro.launch.*`` span's start to the end of the first
    ``repro.fetch`` after it): events that outlast the reach, and the
    program's instructions that run back to back (gaps under
    ``CONTIGUOUS_NS``) with its first or last event in the reach, just
    before or after it. ``[[program, instruction, seconds]]``, largest
    first; None without the tap."""
    launches = _spans(run, LAUNCH)
    table = program_scopes(run) if launches else None
    if table is None:
        return None
    events = sorted(run.trace._window(run.trace.device[0]),
                    key=lambda e: e[1])
    starts = [e[1] for e in events]
    out = []
    for s, f, prog in _reaches(run, launches):
        mine = table.get(prog, {})
        lo = bisect.bisect_left(starts, s)
        hi = bisect.bisect_right(starts, f)
        for name, es, ed in events[lo:hi]:
            if es + ed > f:
                out.append([prog, trace_mod.instruction(name), es + ed - f])
        t = starts[lo] if lo < hi else s  # back from the first in reach
        for name, es, ed in reversed(events[:lo]):
            instr = trace_mod.instruction(name)
            if es + ed < t - CONTIGUOUS_NS or instr not in mine:
                break
            out.append([prog, instr, s - es])
            t = es
        t = max((e[1] + e[2] for e in events[lo:hi]), default=f)
        for name, es, ed in events[hi:]:
            instr = trace_mod.instruction(name)
            if es > t + CONTIGUOUS_NS or instr not in mine:
                break
            out.append([prog, instr, es + ed - f])
            t = max(t, es + ed)
    out = [[p, n, ns * 1e-9] for p, n, ns in out]
    return sorted(out, key=lambda x: -x[2])[:top]
