"""The program's tap (``core/tap.py``): host spans on the profiler's
clock, one ``repro.fetch`` span per host sync, and the named device
scopes the compiled programs carry."""
import contextlib
import glob
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import BipartiteGraph, count_butterflies
from repro.core.peel import peel_tips, peel_wings
from repro.core.pipeline import (
    DEVICE_SCOPES,
    launch,
    record_programs,
    scope,
    span,
)


def _graph():
    rng = np.random.default_rng(7)
    e = np.unique(np.stack([rng.integers(0, 40, 300),
                            rng.integers(0, 30, 300)], 1), axis=0)
    return BipartiteGraph(40, 30, e)


CALLS = {
    "count_butterflies": lambda g: count_butterflies(
        g, mode="all", engine="fused_pallas"),
    "peel_tips": lambda g: peel_tips(
        g, engine="device", peel_mode="range",
        count_kwargs={"engine": "fused_pallas"}),
    "peel_wings": lambda g: peel_wings(
        g, engine="device", peel_mode="range",
        count_kwargs={"engine": "fused_pallas"}),
}


def _host_spans(trace_dir, root):
    """``[(name, start, end)]`` of the ``repro.*`` spans on the host line
    that holds ``root``, by start."""
    path = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)[0]
    pd = jax.profiler.ProfileData.from_file(path)
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                   for e in line.events if e.name.startswith("repro.")]
            if any(n == root for n, _s, _e in evs):
                return sorted(evs, key=lambda x: (x[1], -x[2]))
    raise AssertionError(f"no {root} span in the trace")


@pytest.mark.parametrize("entry", sorted(CALLS))
def test_spans_nest_in_order_and_count_the_syncs(entry, tmp_path,
                                                 monkeypatch):
    g = _graph()
    CALLS[entry](g)  # compile outside the trace
    syncs = []
    orig = jax.device_get
    monkeypatch.setattr(
        jax, "device_get", lambda x: (syncs.append(1), orig(x))[1])
    with jax.profiler.trace(str(tmp_path)):
        CALLS[entry](g)
    spans = _host_spans(tmp_path, "repro." + entry)
    roots = [sp for sp in spans if sp[0] == "repro." + entry]
    assert len(roots) == 1
    _root, r0, r1 = roots[0]
    assert all(r0 <= s and e <= r1 for _n, s, e in spans)
    first = {}
    for n, s, _e in spans:
        key = "repro.launch" if n.startswith("repro.launch.") else n
        first.setdefault(key, s)
    order = ["repro.rank", "repro.preprocess", "repro.plan",
             "repro.launch", "repro.fetch"]
    assert [first[k] for k in order] == sorted(first[k] for k in order)
    # every launch is followed by a fetch that ends after it starts
    launches = [sp for sp in spans if sp[0].startswith("repro.launch.")]
    fetches = [sp for sp in spans if sp[0] == "repro.fetch"]
    assert launches and all(any(f[1] >= la[1] for f in fetches)
                            for la in launches)
    assert len(fetches) == len(syncs) >= 1


def _carried(text):
    """``(with an op_name, carrying a DEVICE_SCOPES entry)`` counts of the
    fusion, custom-call, scatter and sort instructions of a compiled
    module: an instruction's own ``op_name``, else those of the
    computation it calls (a fusion's body)."""
    comps, cur = {}, None
    for line in text.splitlines():
        m = re.match(r"^(?:ENTRY\s+)?%([\w.\-]+)\s.*\{\s*$", line)
        if m:
            cur = comps.setdefault(m.group(1), [])
        elif cur is not None and line.strip() != "}":
            cur.append(line)
    names = {c: re.findall(r'op_name="([^"]*)"', "\n".join(ls))
             for c, ls in comps.items()}
    have = carry = 0
    for line in text.splitlines():
        if not re.match(r"\s*(?:ROOT\s+)?%[\w.\-]+ = .*? "
                        r"(?:fusion|custom-call|scatter|sort)\(", line):
            continue
        ops = re.findall(r'op_name="([^"]*)"', line)
        for c in re.findall(r"calls=%([\w.\-]+)", line):
            ops += names.get(c, [])
        if ops:
            have += 1
            carry += any(set(o.split("/")) & set(DEVICE_SCOPES)
                         for o in ops)
    return have, carry


@pytest.mark.parametrize("entry", sorted(CALLS))
def test_compiled_programs_carry_device_scopes(entry):
    """Of the device work that names its source at all (the compiler
    also makes wrappers with no ``op_name``), at least 90% names a
    scope, in every program the call launches."""
    g = _graph()
    with record_programs() as programs:
        CALLS[entry](g)
    assert programs
    for program, args, kwargs in programs:
        text = program.lower(*args, **kwargs).compile().as_text()
        have, carry = _carried(text)
        assert have >= 20, program.__name__
        assert carry >= 0.9 * have, (program.__name__, carry, have)


def test_launch_names_its_program_and_records_it(tmp_path):
    f = jax.jit(lambda x: x + 1)
    with record_programs() as programs:
        with jax.profiler.trace(str(tmp_path)):
            with span("test_root"):
                out = launch(f, jnp.arange(3))
    assert np.array_equal(np.asarray(out), [1, 2, 3])
    assert programs[0][0] is f
    names = [n for n, _s, _e in _host_spans(tmp_path, "repro.test_root")]
    assert "repro.launch.<lambda>" in names


def test_scope_names_only_device_scopes():
    with pytest.raises(ValueError, match="device scope"):
        scope("fusion")
    text = jax.jit(lambda x: scope("recover")(jnp.cumsum)(x) * 2).lower(
        jnp.arange(8)).as_text(debug_info=True)
    assert "recover" in text


def _compiled_wing_loop(g, drop=()):
    """The compiled text of the wing program a device wing call
    launches, traced with the scopes in ``drop`` left out."""
    import repro.core.peel as pm

    real = pm._scope

    def scoped(name):
        return contextlib.nullcontext() if name in drop else real(name)

    with record_programs() as programs:
        CALLS["peel_wings"](g)
    (program, args, kwargs), = [p for p in programs
                                if p[0].__name__ == "_peel_wings_device"]
    pm._scope = scoped
    try:
        jax.clear_caches()
        return program.lower(*args, **kwargs).compile().as_text()
    finally:
        pm._scope = real
        jax.clear_caches()


def test_member_scope_changes_only_op_names():
    """The ``member`` scope renames the wing loop's membership search
    and changes nothing else of the compiled program."""
    g = _graph()
    with_member = _compiled_wing_loop(g)
    without = _compiled_wing_loop(g, drop=("member",))
    assert "/member/" in with_member and "/member/" not in without

    def strip(text):  # the code: no source tables, no metadata
        code = text[re.search(r"^(%|ENTRY)", text, re.M).start():]
        return re.sub(r",? metadata=\{[^}]*\}", "", code)

    assert strip(with_member) == strip(without)
