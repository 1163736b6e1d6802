#!/usr/bin/env python
"""Summarize / merge torchdistx_tpu telemetry traces.

Traces are the Chrome-trace JSON files `torchdistx_tpu.observe` flushes
into ``TDX_TRACE_DIR`` (one per process — bench phases each run in their
own subprocess, so a bench round leaves several).  Stdlib only: usable on
a login host with no torch/jax installed.

Commands:

``summary <dir-or-file>... [--top N]``
    Human-readable digest of one run: wall span, top span names by
    aggregate self-time, compile-cache hit ratio, dropped-event count,
    serve SLO percentiles, platform-fallback and verification-failure
    counts, final counter/gauge values.

``chrome <dir-or-file>... [-o merged.json]``
    Merge every per-process trace into ONE Chrome-trace JSON loadable in
    ``chrome://tracing`` / Perfetto (timestamps are epoch-anchored, so
    processes land on a shared timeline).

``flight <dump-or-dir>...``
    Render flight-recorder post-mortem dumps (TDX_FLIGHT_DIR bundles):
    schema-validate each, then print reason/time/context, the final
    counter snapshot, and the last spans leading up to the trigger.
    Exit 1 on schema violations.

``fleet <dir>... [--top N]``
    Roll per-host telemetry dirs (traces + flight dumps + ``%h``/pid
    metrics files) into ONE report: per-host compile/fetch/steal counts,
    flight-dump reasons, slowest spans, and fleet-wide totals with serve
    SLO percentiles.  Each argument dir is one host; a single argument
    whose subdirectories hold the telemetry expands to one host per
    subdir (the natural layout for ``TDX_FLIGHT_DIR=/logs/%h``).

``autopsy <request-id> <dir-or-file>...``
    Reconstruct ONE request's life across the whole serve fleet from
    merged telemetry (trace files + flight-dump rings): its ledger
    timeline (enqueue → dispatch → admit/chunk/decode → hedge /
    preempt / requeue hops → finish or typed rejection) interleaved
    with the fleet-side instants carrying the same rid/flow id, plus
    the queue/prefill/decode/guardrail attribution that sums to the
    end-to-end latency by construction.  The terminal ``serve.request``
    instant (emitted by ``observe.reqledger``) is the primary source; a
    request still in flight at crash time is recovered from a flight
    dump's ``ledger.live`` table.

Exit status: 0 on success, 2 when no telemetry was found.
"""

from __future__ import annotations

import argparse
import glob as _glob
import json
import os
import sys
from typing import Dict, Iterator, List, Optional, Tuple

# Mirror of torchdistx_tpu.observe.flightrec.SCHEMA_KEYS — this CLI must
# stay importable with stdlib only (login hosts without torch/jax), so
# it carries its own copy; keep the two in sync.  v2 dumps additionally
# carry the causal identity ("trace_id" / "trace_parent"); v1 dumps stay
# readable.
FLIGHT_SCHEMA_VERSION = 2
FLIGHT_SUPPORTED_SCHEMAS = (1, 2)
FLIGHT_SCHEMA_KEYS = (
    "schema", "reason", "time", "pid", "host", "events", "config",
    "env", "counter_snapshots",
)
FLIGHT_SCHEMA_KEYS_V2 = ("trace_id",)


def iter_trace_files(paths: List[str]) -> Iterator[str]:
    for p in paths:
        if os.path.isdir(p):
            for name in sorted(os.listdir(p)):
                if name.startswith("flight-"):
                    continue  # post-mortem bundles: the `flight`/`fleet` cmds
                if name.endswith(".trace.json") or name.endswith(".json"):
                    yield os.path.join(p, name)
        else:
            yield p


def load_events(paths: List[str]) -> List[dict]:
    events: List[dict] = []
    for path in iter_trace_files(paths):
        try:
            with open(path) as f:
                doc = json.load(f)
        except (OSError, ValueError) as e:
            print(f"warning: skipping {path}: {e}", file=sys.stderr)
            continue
        evs = doc.get("traceEvents") if isinstance(doc, dict) else doc
        if isinstance(evs, list):
            events.extend(e for e in evs if isinstance(e, dict))
    return events


def _final_counters(events: List[dict]) -> Dict[str, float]:
    """Counters are per-process cumulative totals: take the LATEST sample
    (by timestamp — file order is not time order across flushes) of each
    (name, pid) stream, then sum over pids so a multi-process run
    aggregates correctly.  Percentile gauges (``.slo.`` streams) take the
    max instead — a p99 summed over processes is not a p99."""
    last: Dict[tuple, tuple] = {}
    for e in events:
        if e.get("ph") != "C":
            continue
        args = e.get("args") or {}
        value = args.get("value")
        if value is None and "count" in args:  # histogram snapshot
            value = args.get("count")
        if value is None:
            continue
        key = (e.get("name"), e.get("pid"))
        ts = float(e.get("ts", 0.0))
        if key not in last or ts >= last[key][0]:
            last[key] = (ts, float(value), args.get("mtype"))
    out: Dict[str, float] = {}
    for (name, _pid), (_ts, v, mtype) in last.items():
        if v != v:
            continue  # NaN-poisoned gauge (aged-out window): not a value
        if (mtype == "gauge" and _gauge_takes_max(name or "")) \
                or (mtype is None and ".slo." in (name or "")):
            # Singleton gauges (percentiles, link bandwidth, high-water
            # marks) take max over pids — summed they are nonsense; the
            # remaining gauges are per-replica rates/capacities where
            # fleet totals ARE the sum.  Pre-mtype trace files fall
            # back to the .slo. name heuristic.
            out[name] = max(out.get(name, 0.0), v)
        else:
            out[name] = out.get(name, 0.0) + v
    return out


def _fmt_s(v: Optional[float]) -> str:
    if v is None or v != v:  # NaN: a poisoned (aged-out) gauge
        return "-"
    return f"{v * 1e3:.1f}ms" if v < 1.0 else f"{v:.2f}s"


def _slo_digest(counters: Dict[str, float], indent: str = "  ") -> List[str]:
    """Serve SLO percentile lines from the exported gauges, or []."""
    rows = []
    for label, key in (("TTFT", "ttft"), ("per-token", "token"),
                       ("queue wait", "queue_wait")):
        ps = {q: _cg(counters, f"tdx.serve.slo.{key}_p{q}_s")
              for q in (50, 95, 99)}
        ps = {q: (None if v is not None and v != v else v)  # NaN → absent
              for q, v in ps.items()}
        if all(v is None for v in ps.values()):
            continue
        n = _cg(counters, f"tdx.serve.slo.{key}_window_count")
        rows.append(
            f"{indent}{label:<11} p50={_fmt_s(ps[50])} "
            f"p95={_fmt_s(ps[95])} p99={_fmt_s(ps[99])}"
            + (f"  (n={int(n)})" if n else "")
        )
    return ["serve SLOs (sliding window):"] + rows if rows else []


def summarize(events: List[dict], top: int = 15) -> str:
    spans = [e for e in events if e.get("ph") == "X"]
    counters = _final_counters(events)
    lines: List[str] = []

    if spans:
        t0 = min(e["ts"] for e in spans)
        t1 = max(e["ts"] + e.get("dur", 0.0) for e in spans)
        pids = {e.get("pid") for e in spans}
        lines.append(
            f"{len(spans)} spans across {len(pids)} process(es), "
            f"wall {((t1 - t0) / 1e6):.3f} s"
        )
        agg: Dict[str, List[float]] = {}
        for e in spans:
            args = e.get("args") or {}
            self_us = args.get("self_us", e.get("dur", 0.0))
            agg.setdefault(e["name"], [0.0, 0.0, 0.0])
            a = agg[e["name"]]
            a[0] += 1
            a[1] += e.get("dur", 0.0)
            a[2] += self_us
        lines.append("")
        lines.append(f"top spans by aggregate self-time (of {len(agg)}):")
        lines.append(f"  {'name':<28} {'count':>5} {'total_s':>9} {'self_s':>9}")
        ranked = sorted(agg.items(), key=lambda kv: -kv[1][2])[:top]
        for name, (n, tot, self_t) in ranked:
            lines.append(
                f"  {name:<28} {int(n):>5} {tot / 1e6:>9.3f} {self_t / 1e6:>9.3f}"
            )
    else:
        lines.append("no spans found")

    hits = counters.get("tdx.jax.compile_cache_hit", 0.0)
    misses = counters.get("tdx.jax.compile_cache_miss", 0.0)
    uncached = counters.get("tdx.jax.compile_cache_uncached", 0.0)
    lines.append("")
    if hits or misses or uncached:
        denom = hits + misses
        ratio = f"{hits / denom:.0%}" if denom else "n/a"
        lines.append(
            f"compile cache: {int(hits)} hit / {int(misses)} miss "
            f"({ratio} hit ratio)"
            + (f", {int(uncached)} uncached" if uncached else "")
        )
    else:
        lines.append("compile cache: no compile events recorded")

    # Transport digest (docs/performance.md §transport), alongside the
    # cache digest: the achieved materialize rate against the measured
    # link, and how the bytes moved (donated fraction, batched puts,
    # transfer time hidden behind execution).
    gbps = counters.get("tdx.jax.materialize_gbps")
    if gbps:
        parts = [f"transport: {gbps:.3g} GB/s materialize"]
        link = counters.get("tdx.jax.link_bandwidth_gbps")
        if link:
            probe = next(
                (k.split("probe_mb=", 1)[1].rstrip("}")
                 for k in counters
                 if k.startswith("tdx.jax.link_bandwidth_gbps{probe_mb=")),
                None,
            )
            util = counters.get("tdx.jax.link_utilization",
                                gbps / link if link else 0.0)
            parts.append(
                f"{util:.1%} of {link:.2f} GB/s link"
                + (f" (probe {probe} MB)" if probe else "")
            )
        moved = counters.get("tdx.jax.bytes_materialized", 0.0)
        donated = counters.get("tdx.jax.bytes_donated", 0.0)
        if donated:
            frac = f" ({donated / moved:.0%} of materialized)" if moved else ""
            parts.append(f"{donated / 1e6:.3g} MB donated{frac}")
        batches = counters.get("tdx.jax.device_put_batches", 0.0)
        if batches:
            parts.append(f"{int(batches)} batched device_put(s)")
        toverlap = counters.get("tdx.jax.transfer_overlap")
        if toverlap is not None:
            parts.append(f"transfer overlap {toverlap:.2f}")
        lines.append(", ".join(parts))

    # Artifact-registry digest (docs/registry.md vocabulary), alongside
    # the compile-cache ratio it feeds: a healthy pod shows registry
    # fetch hits ≈ compile-cache hits on every host but the publishers.
    r_hit = counters.get("tdx.registry.fetch_hit", 0.0)
    r_miss = counters.get("tdx.registry.fetch_miss", 0.0)
    r_pub = counters.get("tdx.registry.publish", 0.0)
    if r_hit or r_miss or r_pub:
        denom = r_hit + r_miss
        ratio = f"{r_hit / denom:.0%}" if denom else "n/a"
        parts = [
            f"registry: {int(r_hit)} fetch hit / {int(r_miss)} miss "
            f"({ratio} hit ratio), {int(r_pub)} published",
        ]
        for label, key in (("stolen", "tdx.registry.steals"),
                           ("verify failures", "tdx.registry.verify_fail"),
                           ("publish errors", "tdx.registry.publish_errors")):
            v = counters.get(key, 0.0)
            if v:
                parts.append(f"{int(v)} {label}")
        mb_f = counters.get("tdx.registry.bytes_fetched", 0.0) / 1e6
        mb_p = counters.get("tdx.registry.bytes_published", 0.0) / 1e6
        parts.append(f"{mb_f:.1f} MB fetched / {mb_p:.1f} MB published")
        lines.append(", ".join(parts))

    # Silent span loss made loud: events evicted from the in-memory
    # export buffer (tdx.observe.dropped_events counts them live; the
    # tdx.trace.events_dropped stamp rides each flushed file).
    dropped = max(
        counters.get("tdx.observe.dropped_events", 0.0),
        counters.get("tdx.trace.events_dropped", 0.0),
    )
    if dropped:
        lines.append(
            f"WARNING: {int(dropped)} trace event(s) dropped from the "
            f"export buffer (raise the tracer cap or flush more often; "
            f"the flight recorder's ring is unaffected)"
        )

    slo_lines = _slo_digest(counters)
    if slo_lines:
        lines.append("")
        lines.extend(slo_lines)

    dumps = sum(
        v for k, v in counters.items()
        if k.startswith("tdx.observe.flight_dumps")
        and "suppressed" not in k
    )
    if dumps:
        lines.append(f"flight-recorder dumps: {int(dumps)}")

    # The one way a run ends up off the chip without failing: pallas
    # kernels resolved to interpret mode (ops/_interpret.py).
    lines.append(
        "interpreted kernel calls: "
        f"{int(counters.get('tdx.ops.interpreted_calls', 0))}"
    )
    verify = sum(
        v for k, v in counters.items()
        if k.startswith("tdx.graph.verify_failures")
    )
    if verify:
        lines.append(f"replay verification failures: {int(verify)}")

    # Robustness digest (docs/robustness.md vocabulary).  Labeled counters
    # arrive as name{label=...} streams — aggregate back by prefix.
    chaos = sum(
        v for k, v in counters.items() if k.startswith("tdx.chaos.injected")
    )
    rob = [
        ("restarts", counters.get("tdx.elastic.restarts")),
        ("watchdog kills", counters.get("tdx.elastic.watchdog_kills")),
        ("preemption drains", counters.get("tdx.elastic.drains")),
        ("ckpt verify failures", counters.get("tdx.ckpt.verify_fail")),
        ("ckpt quarantined", counters.get("tdx.ckpt.quarantined")),
        ("chaos injected", chaos or None),
    ]
    if any(v is not None for _k, v in rob):
        lines.append(
            "robustness: "
            + ", ".join(f"{k}={int(v or 0)}" for k, v in rob if v is not None)
        )

    interesting = {
        k: v for k, v in sorted(counters.items())
        if not k.startswith("tdx.jax.compile_cache")
    }
    if interesting:
        lines.append("")
        lines.append("counters/gauges (final values, summed over processes):")
        for k, v in interesting.items():
            vs = f"{int(v)}" if v == int(v) else f"{v:.3f}"
            lines.append(f"  {k:<36} {vs}")
    return "\n".join(lines)


def pair_flows(events: List[dict]) -> Tuple[List[dict], int]:
    """Keep only COMPLETE flow-event pairs (a ``ph:"s"`` start and at
    least one ``ph:"f"`` finish sharing (cat, id)); returns the filtered
    list and the dropped count.  Unpaired halves arise when a spawned
    child never flushed (crash before its first span) or when only one
    side's trace dir was collected — half an arrow renders as a dangling
    artifact in Perfetto, so it is dropped and COUNTED, never silently
    kept or silently lost."""
    starts: set = set()
    finishes: set = set()
    for e in events:
        ph = e.get("ph")
        if ph == "s":
            starts.add((e.get("cat"), e.get("id")))
        elif ph == "f":
            finishes.add((e.get("cat"), e.get("id")))
    paired = starts & finishes
    out: List[dict] = []
    dropped = 0
    for e in events:
        if e.get("ph") in ("s", "f") \
                and (e.get("cat"), e.get("id")) not in paired:
            dropped += 1
            continue
        out.append(e)
    return out, dropped


def merge_chrome(events: List[dict]) -> dict:
    events, dropped = pair_flows(events)
    doc = {"traceEvents": events, "displayTimeUnit": "ms"}
    if dropped:
        # Top-level metadata: chrome://tracing ignores unknown keys, the
        # tests and a curious operator can read the count back.
        doc["tdxUnpairedFlowEventsDropped"] = dropped
    return doc


# -- flight-recorder dumps ---------------------------------------------------


def find_flight_dumps(paths: List[str]) -> List[str]:
    out: List[str] = []
    for p in paths:
        if os.path.isdir(p):
            out.extend(sorted(
                _glob.glob(os.path.join(p, "flight-*.json"))
                + _glob.glob(os.path.join(p, "**", "flight-*.json"),
                             recursive=True)
            ))
        elif os.path.basename(p).startswith("flight-"):
            out.append(p)
    # de-dup while keeping order (the two globs overlap on depth-1 dirs)
    seen: set = set()
    return [p for p in out if not (p in seen or seen.add(p))]


def validate_flight(doc: dict) -> List[str]:
    """Stdlib mirror of observe.flightrec.validate (keep in sync)."""
    problems = [f"missing key {k!r}" for k in FLIGHT_SCHEMA_KEYS
                if k not in doc]
    ver = doc.get("schema")
    if ver not in FLIGHT_SUPPORTED_SCHEMAS:
        problems.append(f"unknown schema version {ver!r}")
    elif isinstance(ver, int) and ver >= 2:
        problems.extend(f"missing key {k!r}" for k in FLIGHT_SCHEMA_KEYS_V2
                        if k not in doc)
    if not isinstance(doc.get("events"), list):
        problems.append("events is not a list")
    return problems


def _flight_counters(doc: dict) -> Dict[str, float]:
    """Final counter values carried by a dump (its last snapshot)."""
    snaps = doc.get("counter_snapshots") or []
    out: Dict[str, float] = {}
    if snaps:
        for rec in snaps[-1].get("counters", []):
            v = rec.get("value", rec.get("count"))
            if isinstance(v, (int, float)):
                name = rec["name"]
                if rec.get("labels"):
                    name += "{" + ",".join(
                        f"{k}={v2}" for k, v2 in sorted(rec["labels"].items())
                    ) + "}"
                out[name] = float(v)
    return out


def render_flight(path: str, doc: dict, top: int = 8) -> str:
    import datetime

    lines = [f"== {path}"]
    problems = validate_flight(doc)
    if problems:
        lines.append("  SCHEMA INVALID: " + "; ".join(problems))
        return "\n".join(lines)
    ts = datetime.datetime.fromtimestamp(doc["time"]).isoformat(
        sep=" ", timespec="seconds")
    lines.append(
        f"  reason: {doc['reason']}   at {ts}   "
        f"host={doc['host']} pid={doc['pid']}"
    )
    if doc.get("trace_id"):  # schema v2: causal identity
        tline = f"  trace: {doc['trace_id']}"
        if doc.get("trace_parent"):
            tline += f"   (spawned: parent={doc['trace_parent']})"
        lines.append(tline)
    ctx = doc.get("context") or {}
    if ctx:
        lines.append("  context: " + ", ".join(
            f"{k}={v}" for k, v in sorted(ctx.items())))
    events = doc["events"]
    spans = [e for e in events if e.get("ph") == "X"]
    instants = [e for e in events if e.get("ph") == "i"]
    lines.append(
        f"  ring: {len(events)} events ({len(spans)} spans, "
        f"{len(instants)} instants)"
        + (f", {doc['dropped_events']} dropped upstream"
           if doc.get("dropped_events") else "")
    )
    if spans:
        lines.append(f"  last {min(top, len(spans))} spans before the trigger:")
        for e in spans[-top:]:
            dur = e.get("dur", 0.0) / 1e6
            attrs = e.get("args") or {}
            extra = ", ".join(
                f"{k}={v}" for k, v in attrs.items()
                if k in ("cache", "group", "error", "step", "rid")
            )
            lines.append(
                f"    {e.get('name', '?'):<28} {dur:>9.3f}s"
                + (f"  [{extra}]" if extra else "")
            )
    counters = _flight_counters(doc)
    interesting = {k: v for k, v in sorted(counters.items())
                   if v and not k.startswith("tdx.observe.flight_dumps")}
    if interesting:
        lines.append("  final counters:")
        for k, v in list(interesting.items())[:14]:
            vs = f"{int(v)}" if v == int(v) else f"{v:.3f}"
            lines.append(f"    {k:<40} {vs}")
    return "\n".join(lines)


# -- per-request autopsy -----------------------------------------------------

# The ledger's stage vocabulary (observe/reqledger.py STAGES); the
# attribution contract is that these sum to the end-to-end latency.
AUTOPSY_STAGES = ("queue", "prefill", "decode", "guardrail")


def _merge_event_sources(events: List[dict],
                         flight_docs: List[dict]) -> List[dict]:
    """Trace-file events plus every flight dump's ring, deduplicated:
    the recorder TEES the tracer, so an event that was both flushed and
    dumped appears in both sources with identical fields."""
    seen: set = set()
    out: List[dict] = []
    for e in events + [e for doc in flight_docs
                       for e in doc.get("events", [])
                       if isinstance(e, dict)]:
        key = (e.get("ts"), e.get("ph"), e.get("name"), e.get("pid"),
               e.get("tid"), json.dumps(e.get("args"), sort_keys=True,
                                        default=str))
        if key in seen:
            continue
        seen.add(key)
        out.append(e)
    return out


def _autopsy_detail(events: List[dict],
                    flight_docs: List[dict],
                    rid: str) -> Tuple[Optional[dict], Optional[float]]:
    """The request's ledger detail and (when known) the trace timestamp
    of its terminal instant.  Finished requests ride the ``serve.request``
    instant (args = full detail, events included); a request that was
    still live when a flight dump fired falls back to the dump's
    ``ledger.live`` summary (no timeline, but stage attribution)."""
    best: Optional[Tuple[float, dict]] = None
    for e in events:
        if e.get("ph") != "i" or e.get("name") != "serve.request":
            continue
        a = e.get("args") or {}
        if a.get("rid") != rid:
            continue
        ts = float(e.get("ts", 0.0))
        if best is None or ts >= best[0]:
            best = (ts, a)
    if best is not None:
        return dict(best[1]), best[0]
    for doc in flight_docs:
        for entry in (doc.get("ledger") or {}).get("live", []):
            if isinstance(entry, dict) and entry.get("rid") == rid:
                return dict(entry), None
    return None, None


def _fmt_attrs(attrs: dict, drop=("rid", "flow")) -> str:
    parts = [f"{k}={v}" for k, v in attrs.items()
             if k not in drop and v is not None]
    return "  ".join(parts)


def autopsy_report(events: List[dict], flight_docs: List[dict],
                   rid: str) -> Optional[str]:
    """One request's reconstructed life, or None when the telemetry
    never saw it."""
    detail, end_ts = _autopsy_detail(events, flight_docs, rid)
    flow = detail.get("flow") if detail else None
    related = []
    for e in events:
        if e.get("ph") != "i" or e.get("name") == "serve.request":
            continue
        a = e.get("args") or {}
        if a.get("rid") == rid or (flow is not None and a.get("flow") == flow):
            related.append(e)
    if detail is None and not related:
        return None

    lines = [f"== autopsy: rid={rid}"
             + (f"   flow=0x{flow:x}" if isinstance(flow, int) else "")]
    if detail is None:
        lines.append("  no ledger record (TDX_REQUEST_LEDGER=0, or the "
                     "terminal event left the ring); fleet instants only:")
        for e in sorted(related, key=lambda e: float(e.get("ts", 0.0))):
            lines.append(f"    {e.get('name', '?'):<20} "
                         f"{_fmt_attrs(e.get('args') or {})}")
        return "\n".join(lines)

    outcome = detail.get("outcome")
    head = [f"outcome={outcome if outcome else 'IN FLIGHT (' + str(detail.get('stage')) + ')'}",
            f"attempts={detail.get('attempts', 1)}"]
    if detail.get("hedged"):
        head.append("hedged")
    if detail.get("version") is not None:
        # Which weight version served it — old-vs-new attribution for
        # tail regressions during a blue-green roll (/tail blame).
        head.append(f"version={detail['version']}")
    head.append(f"tokens={detail.get('tokens', 0)}")
    if detail.get("n_prompt") is not None:
        head.append(f"prompt={detail['n_prompt']}")
    if detail.get("prefix_tokens"):
        head.append(f"prefix_hit={detail['prefix_tokens']}")
    if detail.get("cow_copies"):
        head.append(f"cow={detail['cow_copies']}")
    lines.append("  " + "  ".join(head))

    # Speculative-decoding summary (present only when verify ticks ran
    # for this request): how much the drafter proposed, how much
    # survived verify, and the realized accept rate.
    if detail.get("spec_ticks"):
        drafted = int(detail.get("spec_drafted", 0))
        accepted = int(detail.get("spec_accepted", 0))
        rate = f"{accepted / drafted:.1%}" if drafted else "n/a"
        lines.append(
            f"  speculation: drafted={drafted}  accepted={accepted}  "
            f"verify_ticks={detail['spec_ticks']}  accept_rate={rate}")

    e2e = detail.get("e2e_s")
    stage_sum = sum(float(detail.get(f"{st}_s", 0.0))
                    for st in AUTOPSY_STAGES)
    lines.append("  attribution (stages sum to e2e by construction):")
    denom = e2e if e2e else stage_sum
    for st in AUTOPSY_STAGES:
        v = float(detail.get(f"{st}_s", 0.0))
        pct = f"  ({v / denom:.1%})" if denom else ""
        lines.append(f"    {st:<10} {v:>11.6f}s{pct}")
    if e2e is not None:
        lines.append(
            f"    {'e2e':<10} {float(e2e):>11.6f}s  "
            f"(stages sum {stage_sum:.6f}s, "
            f"residual {abs(float(e2e) - stage_sum):.6f}s)"
        )

    # One merged timeline: ledger events are relative to the request's
    # t0 already; fleet/replica instants are re-anchored onto the same
    # clock via the terminal instant (its ts marks t0 + e2e).
    rows: List[Tuple[float, str, str]] = []
    for ev in detail.get("events", []) or []:
        attrs = {k: v for k, v in ev.items() if k not in ("t", "k")}
        rows.append((float(ev.get("t", 0.0)), ev.get("k", "?"),
                     _fmt_attrs(attrs)))
    t0_us = (end_ts - float(e2e) * 1e6
             if end_ts is not None and e2e is not None else None)
    unanchored = 0
    for e in sorted(related, key=lambda e: float(e.get("ts", 0.0))):
        label = e.get("name", "?")
        attrs = _fmt_attrs(e.get("args") or {})
        if t0_us is not None:
            rows.append(((float(e.get("ts", 0.0)) - t0_us) / 1e6,
                         label, attrs))
        else:
            unanchored += 1
            lines.append(f"    [unanchored] {label:<18} {attrs}")
    rows.sort(key=lambda r: r[0])
    if rows:
        lines.append(f"  timeline ({len(rows)} events"
                     + (f", {unanchored} unanchored" if unanchored else "")
                     + "):")
        for t, kind, attrs in rows:
            lines.append(f"    {t:>+11.6f}s  {kind:<18} {attrs}")
    if detail.get("events_dropped"):
        lines.append(f"  ({detail['events_dropped']} ledger event(s) "
                     f"dropped at the per-request cap)")
    return "\n".join(lines)


# -- fleet rollup ------------------------------------------------------------

# Gauges where max-over-processes is the honest rollup: percentiles,
# measured link bandwidth, high-water marks, per-step figures — summing
# any of these across pids is nonsense (3 processes probing one link is
# not 3x the bandwidth).  The REMAINING gauges are per-replica
# rates/capacities (tokens_per_s, queue_depth, kv_pages_in_use) where
# fleet totals ARE the sum, like counters.
_GAUGE_MAX_PREFIXES = (
    "tdx.serve.slo.", "tdx.jax.link_", "tdx.jax.hbm_high_water",
    "tdx.jax.materialize_gbps", "tdx.jax.transfer_overlap",
    "tdx.jax.pipeline_overlap", "tdx.train.mfu", "tdx.train.step_ms",
    "tdx.train.tflops",
)


def _gauge_takes_max(name: str) -> bool:
    base = name.split("{", 1)[0]
    return any(base.startswith(p) or base.startswith(_prom_name(p))
               for p in _GAUGE_MAX_PREFIXES)


def _load_one_metrics_file(path: str) -> Tuple[Dict[str, float],
                                               Dict[str, str]]:
    """One exported metrics file → ({name: value}, {base_name: type}).
    Within one file last-write-wins is correct (a process re-exports its
    own totals); aggregation across files happens in the caller."""
    out: Dict[str, float] = {}
    types: Dict[str, str] = {}
    last_ts: Dict[str, float] = {}
    if path.endswith(".prom"):
        with open(path) as f:
            for line in f:
                line = line.strip()
                if line.startswith("# TYPE "):
                    parts = line.split()
                    if len(parts) == 4:
                        types[parts[2]] = parts[3]
                    continue
                if not line or line.startswith("#"):
                    continue
                parts = line.rsplit(" ", 1)
                if len(parts) != 2:
                    continue
                try:
                    out[parts[0]] = float(parts[1])
                except ValueError:
                    continue
    else:
        with open(path) as f:
            for line in f:
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue
                name = rec.get("name")
                v = rec.get("value", rec.get("count"))
                if name is None or not isinstance(v, (int, float)):
                    continue
                if rec.get("type"):
                    types[name] = rec["type"]
                if rec.get("labels"):
                    # Labeled streams must stay distinct (and keyed like
                    # the trace/flight spellings, so _canon_key dedupes
                    # instead of the bare name double-counting).
                    name += "{" + ",".join(
                        f"{k}={v2}" for k, v2 in
                        sorted(rec["labels"].items())
                    ) + "}"
                ts = float(rec.get("ts", 0.0))
                if ts >= last_ts.get(name, -1.0):
                    last_ts[name] = ts
                    out[name] = float(v)
    return out, types


def _load_metrics_files(host_dir: str) -> Dict[str, float]:
    """Final counter values from exported metrics files under one host
    dir (names arrive sanitized from .prom — stored as-is; lookups go
    through _ck).  With ``%p`` templating one host dir holds one file
    PER PROCESS: counters/histograms sum across files, gauges follow
    :func:`_gauge_takes_max` — last-write-wins across pids would keep
    one arbitrary process and drop the rest."""
    out: Dict[str, float] = {}
    for path in sorted(
        _glob.glob(os.path.join(host_dir, "*.jsonl"))
        + _glob.glob(os.path.join(host_dir, "*.prom"))
    ):
        try:
            vals, types = _load_one_metrics_file(path)
        except OSError as e:
            print(f"warning: skipping {path}: {e}", file=sys.stderr)
            continue
        for name, v in vals.items():
            if v != v:
                continue  # NaN-poisoned gauge: not a value
            base = name.split("{", 1)[0]
            if name not in out:
                out[name] = v
            elif types.get(base) == "gauge" and _gauge_takes_max(name):
                out[name] = max(out[name], v)
            else:
                out[name] = out[name] + v
    return out


def _prom_name(name: str) -> str:
    import re

    return re.sub(r"[^a-zA-Z0-9_:]", "_", name)


def _canon_key(key: str) -> str:
    """Canonical counter key: Prometheus-sanitized metric name, label
    values unquoted.  Trace/flight sources carry ``tdx.chaos.injected
    {kind=raise}`` while .prom exports carry ``tdx_chaos_injected
    {kind="raise"}`` — canonicalizing BOTH at merge time lets
    ``setdefault`` dedupe the same stream across source formats (else
    ``_ck`` would sum the two spellings and double-count)."""
    name, sep, rest = key.partition("{")
    return _prom_name(name) + ((sep + rest.replace('"', "")) if sep else "")


def _cg(counters: Dict[str, float], name: str) -> Optional[float]:
    """Single-value lookup tolerant of Prometheus-sanitized names;
    None when absent (``_ck`` coerces to 0 and sums labels)."""
    v = counters.get(name)
    return v if v is not None else counters.get(_prom_name(name))


def _ck(counters: Dict[str, float], name: str) -> float:
    """Counter lookup tolerant of Prometheus-sanitized names (and of
    labeled streams: ``name{...}`` variants are summed in).  Assumes
    label keys are canonical (``_canon_key``) OR come from a single
    source format — never both spellings of one stream."""
    base = _cg(counters, name) or 0.0
    dotted, sanitized = name + "{", _prom_name(name) + "{"
    labeled = sum(
        val for key, val in counters.items()
        if key.startswith(dotted)
        or (sanitized != dotted and key.startswith(sanitized))
    )
    return base + labeled


def _expand_hosts(paths: List[str]) -> List[Tuple[str, str]]:
    """(host_name, dir) pairs.  Each arg dir is a host; a SINGLE arg dir
    with no telemetry of its own but telemetry-bearing subdirs expands
    to one host per subdir (the ``/logs/%h`` layout)."""
    def has_telemetry(d: str) -> bool:
        try:
            names = os.listdir(d)
        except OSError:
            return False
        return any(
            n.endswith((".trace.json", ".prom", ".jsonl"))
            or n.startswith("flight-")
            for n in names
        )

    if len(paths) == 1 and os.path.isdir(paths[0]) and not has_telemetry(paths[0]):
        subs = [
            (n, os.path.join(paths[0], n))
            for n in sorted(os.listdir(paths[0]))
            if os.path.isdir(os.path.join(paths[0], n))
        ]
        subs = [(n, d) for n, d in subs if has_telemetry(d)]
        if subs:
            return subs
    return [(os.path.basename(os.path.normpath(p)) or p, p) for p in paths]


def fleet_report(paths: List[str], top: int = 3) -> Tuple[str, int]:
    """The multi-host rollup; returns (text, n_sources)."""
    hosts = _expand_hosts(paths)
    lines: List[str] = []
    totals: Dict[str, float] = {}
    n_sources = 0
    rows = []
    slo_sections: List[str] = []
    for host, d in hosts:
        events = load_events([d]) if os.path.isdir(d) else []
        dumps = []
        for p in find_flight_dumps([d]):
            try:
                with open(p) as f:
                    dumps.append(json.load(f))
            except (OSError, ValueError) as e:
                print(f"warning: skipping {p}: {e}", file=sys.stderr)
        counters = {
            _canon_key(k): v for k, v in _final_counters(events).items()
        }
        # Fill gaps from exported metrics files, then flight snapshots
        # (trace-final values win: they are flushed last); canonical
        # keys make the dedupe hold across source formats.
        for src in (_load_metrics_files(d) if os.path.isdir(d) else {},
                    *map(_flight_counters, dumps)):
            for k, v in src.items():
                counters.setdefault(_canon_key(k), v)
        if not events and not dumps and not counters:
            continue
        n_sources += 1
        spans = [e for e in events if e.get("ph") == "X"]
        slowest = sorted(spans, key=lambda e: -e.get("dur", 0.0))[:top]
        reasons: Dict[str, int] = {}
        for doc in dumps:
            r = doc.get("reason", "?")
            reasons[r] = reasons.get(r, 0) + 1
        reg_spans: Dict[str, Dict[str, int]] = {}
        for e in spans:
            if e.get("name") in ("registry.publish", "registry.fetch"):
                k = (e.get("args") or {}).get("key")
                if k:
                    per = reg_spans.setdefault(str(k), {})
                    per[e["name"]] = per.get(e["name"], 0) + 1
        # Per-replica weight versions (latest dump's /readyz body wins):
        # a half-rolled fleet shows up as two versions side by side.
        versions: Dict[str, str] = {}
        for doc in dumps:
            replicas = ((doc.get("health") or {}).get("fleet") or {}).get(
                "replicas") or {}
            got = {r: str(info["version"]) for r, info in replicas.items()
                   if isinstance(info, dict) and info.get("version")}
            if got:
                versions = got
        row = {
            "host": host,
            "spans": len(spans),
            "hit": _ck(counters, "tdx.jax.compile_cache_hit"),
            "miss": _ck(counters, "tdx.jax.compile_cache_miss"),
            "fetch": _ck(counters, "tdx.registry.fetch_hit"),
            "steal": _ck(counters, "tdx.registry.steals"),
            "chaos": _ck(counters, "tdx.chaos.injected"),
            "dumps": len(dumps),
            "reasons": reasons,
            "slowest": slowest,
            "reg_spans": reg_spans,
            "versions": versions,
        }
        rows.append(row)
        for k in ("hit", "miss", "fetch", "steal", "chaos"):
            totals[k] = totals.get(k, 0.0) + row[k]
        totals["dumps"] = totals.get("dumps", 0.0) + len(dumps)
        host_slo = _slo_digest(counters, indent="    ")
        if host_slo:
            slo_sections.append(f"  {host}:")
            slo_sections.extend(host_slo[1:])
    if not rows:
        return "", 0
    lines.append(f"fleet: {len(rows)} host(s)")
    lines.append("")
    lines.append(
        f"  {'host':<16} {'spans':>6} {'c.hit':>6} {'c.miss':>6} "
        f"{'r.fetch':>7} {'steals':>6} {'chaos':>6} {'dumps':>6}"
    )
    for r in rows:
        lines.append(
            f"  {r['host']:<16} {r['spans']:>6} {int(r['hit']):>6} "
            f"{int(r['miss']):>6} {int(r['fetch']):>7} {int(r['steal']):>6} "
            f"{int(r['chaos']):>6} {r['dumps']:>6}"
        )
    lines.append(
        f"  {'TOTAL':<16} {'':>6} {int(totals.get('hit', 0)):>6} "
        f"{int(totals.get('miss', 0)):>6} {int(totals.get('fetch', 0)):>7} "
        f"{int(totals.get('steal', 0)):>6} {int(totals.get('chaos', 0)):>6} "
        f"{int(totals.get('dumps', 0)):>6}"
    )
    dump_rows = [r for r in rows if r["reasons"]]
    if dump_rows:
        lines.append("")
        lines.append("flight dumps by reason:")
        for r in dump_rows:
            body = ", ".join(f"{k}×{v}" for k, v in sorted(r["reasons"].items()))
            lines.append(f"  {r['host']:<16} {body}")
    ver_rows = [r for r in rows if r["versions"]]
    if ver_rows:
        lines.append("")
        lines.append("serving weight versions (per replica, from /readyz):")
        for r in ver_rows:
            by_ver: Dict[str, List[str]] = {}
            for rep, ver in sorted(r["versions"].items()):
                by_ver.setdefault(ver, []).append(rep)
            body = "  ".join(f"{v} [{', '.join(reps)}]"
                             for v, reps in sorted(by_ver.items()))
            mixed = "  ** MID-ROLL **" if len(by_ver) > 1 else ""
            lines.append(f"  {r['host']:<16} {body}{mixed}")
    if slo_sections:
        lines.append("")
        lines.append("serve SLOs per host (sliding window):")
        lines.extend(slo_sections)
    # Cross-host causal registry links: the same 12-char registry key
    # published on one host and fetched on another IS a causal edge —
    # host A's compile fed host B's warm.  Spans carry key=key[:12]
    # (registry/store.py) precisely so this join works fleet-wide.
    pub_hosts: Dict[str, List[str]] = {}
    fetch_hosts: Dict[str, List[Tuple[str, int]]] = {}
    for r in rows:
        for key, per in r["reg_spans"].items():
            if per.get("registry.publish"):
                pub_hosts.setdefault(key, []).append(r["host"])
            n_fetch = per.get("registry.fetch", 0)
            if n_fetch:
                fetch_hosts.setdefault(key, []).append((r["host"], n_fetch))
    links = []
    for key in sorted(fetch_hosts):
        for pub_host in pub_hosts.get(key, []):
            for fetch_host, n in fetch_hosts[key]:
                if fetch_host != pub_host:
                    links.append((key, pub_host, fetch_host, n))
    if links:
        lines.append("")
        lines.append("cross-host registry links (publish → fetch by key):")
        for key, pub_host, fetch_host, n in links[:20]:
            times = f" ×{n}" if n > 1 else ""
            lines.append(
                f"  {key:<14} {pub_host} → {fetch_host}{times}"
            )
        if len(links) > 20:
            lines.append(f"  ... and {len(links) - 20} more")
    slow_rows = [(r["host"], e) for r in rows for e in r["slowest"]]
    slow_rows.sort(key=lambda he: -he[1].get("dur", 0.0))
    if slow_rows:
        lines.append("")
        lines.append(f"slowest spans fleet-wide (top {top} per host):")
        for host, e in slow_rows[: 3 * top]:
            lines.append(
                f"  {host:<16} {e.get('name', '?'):<28} "
                f"{e.get('dur', 0.0) / 1e6:>9.3f}s"
            )
    return "\n".join(lines), n_sources


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="tdx_trace", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    sub = ap.add_subparsers(dest="cmd", required=True)
    ps = sub.add_parser("summary", help="digest a trace dir/file")
    ps.add_argument("paths", nargs="+")
    ps.add_argument("--top", type=int, default=15)
    pc = sub.add_parser("chrome", help="merge into one Chrome-trace JSON")
    pc.add_argument("paths", nargs="+")
    pc.add_argument("-o", "--output", default=None,
                    help="output file (default: stdout)")
    pf = sub.add_parser("flight", help="render flight-recorder dumps")
    pf.add_argument("paths", nargs="+")
    pf.add_argument("--top", type=int, default=8,
                    help="spans shown per dump")
    pl = sub.add_parser("fleet", help="roll per-host telemetry dirs up")
    pl.add_argument("paths", nargs="+")
    pl.add_argument("--top", type=int, default=3,
                    help="slowest spans per host")
    pa = sub.add_parser(
        "autopsy", help="reconstruct one request's life across the fleet")
    pa.add_argument("rid", help="the request id to reconstruct")
    pa.add_argument("paths", nargs="+")
    args = ap.parse_args(argv)

    if args.cmd == "autopsy":
        events = load_events(args.paths)
        docs: List[dict] = []
        for path in find_flight_dumps(args.paths):
            try:
                with open(path) as f:
                    docs.append(json.load(f))
            except (OSError, ValueError) as e:
                print(f"warning: skipping {path}: {e}", file=sys.stderr)
        if not events and not docs:
            print("no telemetry found", file=sys.stderr)
            return 2
        text = autopsy_report(
            _merge_event_sources(events, docs), docs, args.rid)
        if text is None:
            print(f"request {args.rid!r} not found in telemetry",
                  file=sys.stderr)
            return 2
        print(text)
        return 0

    if args.cmd == "flight":
        dump_paths = find_flight_dumps(args.paths)
        if not dump_paths:
            print("no flight dumps found", file=sys.stderr)
            return 2
        bad = 0
        for path in dump_paths:
            try:
                with open(path) as f:
                    doc = json.load(f)
            except (OSError, ValueError) as e:
                print(f"== {path}\n  UNREADABLE: {e}")
                bad += 1
                continue
            if validate_flight(doc):
                bad += 1
            print(render_flight(path, doc, top=args.top))
        print(f"{len(dump_paths)} dump(s), {bad} invalid")
        return 1 if bad else 0

    if args.cmd == "fleet":
        text, n = fleet_report(args.paths, top=args.top)
        if not n:
            print("no telemetry found", file=sys.stderr)
            return 2
        print(text)
        return 0

    events = load_events(args.paths)
    if not events:
        print("no trace events found", file=sys.stderr)
        return 2
    if args.cmd == "summary":
        print(summarize(events, top=args.top))
    else:
        doc = merge_chrome(events)
        if args.output:
            with open(args.output, "w") as f:
                json.dump(doc, f)
                f.write("\n")
            note = ""
            if doc.get("tdxUnpairedFlowEventsDropped"):
                note = (f", {doc['tdxUnpairedFlowEventsDropped']} unpaired"
                        " flow event(s) dropped")
            print(f"wrote {args.output} "
                  f"({len(doc['traceEvents'])} events{note})")
        else:
            json.dump(doc, sys.stdout)
            print()
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except BrokenPipeError:  # `tdx_trace ... | head` is a normal usage
        sys.exit(0)
