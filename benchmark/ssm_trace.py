"""Device time of the events that lie under a ``jax.named_scope``: the
Mamba decode update (``tdx_ssm_decode_update``) and the prefill's scan
over time (``tdx_ssm_chunk_scan``), which the program computes with XLA
operations, not with a kernel of its own name.

A scope's name is in the ``op_name`` metadata of the compiled module's
instructions and in no event of the profiler's trace (PERF.md 5), so the
two are joined here: ``scoped_instructions`` reads each compiled
program's text for the instructions whose ``op_name`` holds a scope's
name, under the module's name (a fusion is taken by what it holds, not by
the one name XLA gives it: it counts where at least half of the
instructions of its fused computation that carry a name carry the
scope's; a fusion that holds both kinds is also listed as ``mixed``, with
its two counts, and ``reduce`` gives each one's time and the scope's time
with none and with all of them taken, so that a reader of the line sees
how far the number rests on that rule); ``reduce_dir`` reads the trace's
``XLA Modules`` line (one event a program run, named
``<module>(<fingerprint>)``) and ``XLA Ops`` line (one event an executed
instruction, named by its HLO text), gives each operation the module
whose run encloses it, and takes the UNION of the intervals of the
scoped ones: a ``while`` encloses its body's events, and a union counts
that time once.  Cut to the span of the ``bench.step`` annotations, as
``benchmark/xplane.py`` cuts busy time.

A program without the scopes (a parent commit), a trace without the two
lines, or no trace at all give None, and the readers leave their metrics
out of the line."""

from __future__ import annotations

import bisect
import glob
import os
import re

SCOPES = ("tdx_ssm_decode_update", "tdx_ssm_chunk_scan")
_MODULE = re.compile(r"^HloModule\s+([^\s,]+)")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([A-Za-z0-9_.\-]+)\s*=\s.*"
                    r"op_name=\"([^\"]*)\"")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([A-Za-z0-9_.\-]+)\s+\(.*->.*\{\s*$")
_CALLS = re.compile(r"\bcalls=%?([A-Za-z0-9_.\-]+)")
_EVENT = re.compile(r"^%?([^\s=]+)\s*=")
_RUN = re.compile(r"^(.*)\(\d+\)$")


def _module_of(run_name: str) -> str:
    """``jit_tdx_serve_decode(1234)`` -> ``jit_tdx_serve_decode``."""
    m = _RUN.match(run_name)
    return m[1] if m else run_name


def scoped_in_text(text: str):
    """(module name, {scope: set of instruction names}, {scope: {fusion:
    (instructions of the scope, instructions named)}} for the fusions that
    hold both kinds) of one compiled module's text."""
    module, found = None, {s: set() for s in SCOPES}
    mixed = {s: {} for s in SCOPES}
    inside = None             # the computation a line belongs to
    named = {}                # computation -> [names carried, per scope...]
    fusions = []              # (instruction, computation it calls)
    for line in text.splitlines():
        if module is None:
            m = _MODULE.match(line)
            if m:
                module = m[1]
                continue
        m = _COMPUTATION.match(line)
        if m:
            inside = m[1]
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        count = named.setdefault(inside, [0] + [0] * len(SCOPES))
        count[0] += 1
        for i, s in enumerate(SCOPES):
            if s in m[2]:
                found[s].add(m[1])
                count[i + 1] += 1
        if " fusion(" in line:
            c = _CALLS.search(line)
            if c:
                fusions.append((m[1], c[1]))
    for instr, called in fusions:
        count = named.get(called)
        for i, s in enumerate(SCOPES):
            found[s].discard(instr)
            if count and 2 * count[i + 1] >= count[0] > 0:
                found[s].add(instr)
            if count and 0 < count[i + 1] < count[0]:
                mixed[s][instr] = (count[i + 1], count[0])
    return module, found, mixed


def scoped_instructions(programs: dict) -> dict:
    """{module name: {scope: [instruction names], "mixed": {scope: {fusion:
    [of the scope, named]}}}} over the engine's compiled programs; a
    program whose text cannot be read is left out."""
    out = {}
    for prog in programs.values():
        try:
            module, found, mixed = scoped_in_text(prog.as_text())
        except Exception:  # noqa: BLE001 -- a reader finds nothing, never raises
            continue
        if module and any(found.values()):
            out[module] = {s: sorted(v) for s, v in found.items()}
            out[module]["mixed"] = {s: {k: list(v) for k, v in m.items()}
                                    for s, m in mixed.items() if m}
    return out


def reduce(modules, ops, scoped: dict, lo=None, hi=None) -> dict:
    """``modules`` [(name, start, end)] of the ``XLA Modules`` line,
    ``ops`` [(hlo text, start, end)] of ``XLA Ops``, in ns ->
    {scope: {"seconds", "events"}}, and where fusions hold instructions
    of the scope beside others, also "mixed" (each one's module, counts,
    whether the rule took it, its own seconds and events) and the
    scope's "seconds_no_mixed" / "seconds_all_mixed"."""
    from benchmark import xplane

    def seconds(iv):
        if lo is not None:
            iv = xplane.clip(iv, lo, hi)
        return sum(e - s for s, e in xplane.union(iv)) / 1e9

    runs = sorted((s, e, _module_of(n)) for n, s, e in modules)
    starts = [r[0] for r in runs]
    hits = {s: [] for s in SCOPES}
    mixed = {s: {} for s in SCOPES}   # (module, fusion) -> its intervals
    for hlo, s, e in ops:
        i = bisect.bisect_right(starts, s) - 1
        if i < 0 or s > runs[i][1]:
            continue
        names = scoped.get(runs[i][2])
        m = _EVENT.match(hlo)
        if not names or not m:
            continue
        for scope in SCOPES:
            if m[1] in names[scope]:
                hits[scope].append((s, e))
            if m[1] in names.get("mixed", {}).get(scope, {}):
                mixed[scope].setdefault((runs[i][2], m[1]), []).append((s, e))
    out = {}
    for scope, iv in hits.items():
        out[scope] = {"seconds": seconds(iv), "events": len(iv)}
        if not mixed[scope]:
            continue
        rows, some, every = [], set(), []
        for (module, instr), ivs in sorted(mixed[scope].items()):
            k, n = scoped[module]["mixed"][scope][instr]
            taken = instr in scoped[module][scope]
            rows.append({"module": module, "fusion": instr, "of_scope": k,
                         "named": n, "taken": taken,
                         "seconds": seconds(ivs), "events": len(ivs)})
            some.update(ivs if taken else ())
            every.extend(() if taken else ivs)
        out[scope]["mixed"] = rows
        out[scope]["seconds_no_mixed"] = seconds(
            [x for x in iv if x not in some])
        out[scope]["seconds_all_mixed"] = seconds(iv + every)
    return out


def load(path: str):
    """(modules, ops, (lo, hi) of the ``bench.step`` annotations or None)
    of the first device plane of a ``.xplane.pb``."""
    import jax

    from benchmark import xplane

    pd = jax.profiler.ProfileData.from_file(path)
    modules, ops, steps = [], [], []
    for plane in pd.planes:
        if xplane.DEVICE_PLANE.match(plane.name) and not ops:
            for line in plane.lines:
                into = {"XLA Modules": modules, xplane.OPS_LINE: ops}.get(
                    line.name)
                if into is not None:
                    into.extend((e.name, e.start_ns,
                                 e.start_ns + e.duration_ns)
                                for e in line.events)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                steps.extend((e.start_ns, e.start_ns + e.duration_ns)
                             for e in line.events if e.name == xplane.STEP)
    span = (min(s for s, _ in steps), max(e for _, e in steps)) if steps else None
    return modules, ops, span


def reduce_dir(trace_dir: str, scoped):
    """The reduction of the newest trace under ``trace_dir`` (which is left
    in place), or None."""
    if not scoped:
        return None
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        return None
    try:
        modules, ops, span = load(files[-1])
    except Exception:  # noqa: BLE001
        return None
    if not modules or not ops:
        return None
    out = reduce(modules, ops, scoped, *(span or (None, None)))
    out["modules_seen"] = sorted({_module_of(n) for n, _, _ in modules})
    return out
