"""Summarize a JSONL span trace: per-op time/bytes table.

Usage:
    python scripts/trace_view.py TRACE.jsonl [--chrome OUT.json]
                                             [--cat CAT] [--json]
    python scripts/trace_view.py TRACE.jsonl --traces
    python scripts/trace_view.py TRACE.jsonl --trace ID [--json]
    python scripts/trace_view.py RUNDIR [--traces | --trace ID] [--json]

TRACE.jsonl is what a run writes under MRTPU_TRACE=path (or
MapReduce(trace=path)).  --chrome additionally writes the
Perfetto-loadable Chrome trace-event file; --cat filters to one span
category (mr_op / shuffle / ingest / oink / app / host); --json prints
the aggregate as JSON instead of the table.

A DIRECTORY path is a multi-process run dir (scripts/mrlaunch.py):
every ``trace-r<rank>.jsonl`` shard is indexed as ONE run — each
rank's private ``ts`` epoch is rebased onto the shared wall clock (the
events' ``wall`` field), span ids are namespaced per rank so parent
links cannot collide, and --trace additionally renders the per-rank
timeline plus the collective sync-point alignment table (arrival
spread, slowest rank, attributed cause) from the run dir's
``rank<k>.sync.jsonl`` records.  All ranks of an mrlaunch run share
one trace id (``launch.json``'s ``trace_id``), so ``--trace`` shows
the whole fleet's request.

--traces lists the request trace ids in the file (obs/context.py: a
serve session, a top-level OINK run, or the process context) with span
counts and wall time; --trace ID filters to ONE request and prints its
per-op table, cost roll-up and CRITICAL PATH — the chain of
longest-child spans under the request's longest top-level span, with
per-hop self time, i.e. where the request's wall actually went.
"""
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))


_BYTE_ARGS = ("shuffle_sent_bytes", "shuffle_pad_bytes",
              "spill_write_bytes", "spill_read_bytes")


def read_trace_dir(path: str):
    """Merge a run dir's per-rank shards (``trace-r<k>.jsonl``) into
    one event stream: ``(events, n_shards)``.

    Every process's ``ts`` is microseconds from its OWN perf_counter
    epoch — meaningless across processes.  Each event also carries
    ``wall`` (absolute wall-clock seconds of span start), so each
    shard gets one offset rebasing its whole timeline onto the run's
    shared clock (relative placement within a shard is preserved
    exactly).  Span ids are namespaced per rank — two ranks' span #7
    must not merge into one parent chain — and every event gains a
    top-level ``rank``."""
    import glob
    from gpu_mapreduce_tpu.obs import read_jsonl
    per_rank = []
    for sp in sorted(glob.glob(os.path.join(path, "trace-r*.jsonl"))):
        base = os.path.basename(sp)
        try:
            rank = int(base[len("trace-r"):-len(".jsonl")])
        except ValueError:
            continue
        per_rank.append((rank, read_jsonl(sp)))
    # the run's zero: the earliest shard epoch (wall minus its own ts)
    t0 = None
    for _r, evs in per_rank:
        for ev in evs:
            w = ev.get("wall")
            if w is not None:
                w0 = float(w) - float(ev.get("ts", 0.0)) / 1e6
                t0 = w0 if t0 is None else min(t0, w0)
    out = []
    for rank, evs in per_rank:
        off = None
        if t0 is not None:
            for ev in evs:
                w = ev.get("wall")
                if w is not None:
                    off = (float(w) - t0) * 1e6 \
                        - float(ev.get("ts", 0.0))
                    break
        ns = (rank + 1) << 32
        for ev in evs:
            ev = dict(ev)
            ev["rank"] = rank
            if off is not None:
                ev["ts"] = round(float(ev.get("ts", 0.0)) + off, 1)
            if ev.get("id"):
                ev["id"] = int(ev["id"]) + ns
            if ev.get("parent"):
                ev["parent"] = int(ev["parent"]) + ns
            out.append(ev)
    out.sort(key=lambda e: float(e.get("ts", 0.0)))
    return out, len(per_rank)


def rank_timeline(events) -> dict:
    """{rank: {spans, start_s, end_s, wall_s}} over a merged stream —
    the per-rank lanes of the stitched timeline."""
    out = {}
    for ev in events:
        r = ev.get("rank")
        if r is None:
            r = (ev.get("args") or {}).get("rank")
        if r is None:
            continue
        row = out.setdefault(int(r), {"spans": 0, "_t0": None,
                                      "_t1": None})
        row["spans"] += 1
        a = float(ev.get("ts", 0.0))
        b = a + float(ev.get("dur", 0.0))
        row["_t0"] = a if row["_t0"] is None else min(row["_t0"], a)
        row["_t1"] = b if row["_t1"] is None else max(row["_t1"], b)
    for row in out.values():
        t0v, t1v = row.pop("_t0") or 0.0, row.pop("_t1") or 0.0
        row["start_s"] = round(t0v / 1e6, 6)
        row["end_s"] = round(t1v / 1e6, 6)
        row["wall_s"] = round((t1v - t0v) / 1e6, 6)
    return out


def sync_alignment(rundir: str) -> list:
    """The run's collective sync points, deduped across the ranks that
    each recorded the same (gen, site, seq): spread, slowest rank,
    attributed cause — the per-sync-point rank alignment the stitched
    timeline is read against."""
    from gpu_mapreduce_tpu.obs.fleetobs import read_sync_records
    best = {}
    for rec in read_sync_records(rundir):
        if rec.get("kind") != "spread":
            continue
        key = (rec.get("gen"), rec.get("site"), rec.get("seq"))
        cur = best.get(key)
        if cur is None or rec.get("ranks_seen", 0) > \
                cur.get("ranks_seen", 0):
            best[key] = rec
    return [best[k] for k in sorted(best, key=lambda k: (str(k[0]),
                                                         str(k[1]),
                                                         k[2] or 0))]


def dist_report(events, rundir: str) -> str:
    """The merged-run appendix: per-rank lanes + sync alignment."""
    lines = ["", "per-rank timeline:"]
    tl = rank_timeline(events)
    for r in sorted(tl):
        row = tl[r]
        lines.append(f"  rank {r}: {row['spans']:6d} spans  "
                     f"[{row['start_s']:.4f}s – {row['end_s']:.4f}s]  "
                     f"{row['wall_s']:.4f}s wall")
    if not tl:
        lines.append("  (no rank-tagged events)")
    syncs = sync_alignment(rundir)
    lines += ["", "sync points (arrival spread across ranks):"]
    if not syncs:
        lines.append("  (no sync records under this run dir)")
    for rec in syncs:
        arr = rec.get("arrivals") or {}
        lanes = " ".join(f"r{k}+{v:.3f}s"
                         for k, v in sorted(arr.items(),
                                            key=lambda kv: kv[1]))
        lines.append(f"  {rec.get('site'):12s} #{rec.get('seq')}"
                     f"  spread {rec.get('spread_s', 0.0):.4f}s"
                     f"  slowest r{rec.get('slowest')}"
                     f"  cause {rec.get('cause')}  [{lanes}]")
    return "\n".join(lines)


def trace_index(events) -> dict:
    """{trace_id: {spans, top_spans, wall_s}} over a span stream."""
    out = {}
    for ev in events:
        tid = ev.get("trace")
        if not tid:
            continue
        row = out.setdefault(tid, {"spans": 0, "top_spans": 0,
                                   "_t0": None, "_t1": None})
        row["spans"] += 1
        if not ev.get("parent"):
            row["top_spans"] += 1
        t0 = float(ev.get("ts", 0.0))
        t1 = t0 + float(ev.get("dur", 0.0))
        row["_t0"] = t0 if row["_t0"] is None else min(row["_t0"], t0)
        row["_t1"] = t1 if row["_t1"] is None else max(row["_t1"], t1)
    for row in out.values():
        row["wall_s"] = round(((row.pop("_t1") or 0.0)
                               - (row.pop("_t0") or 0.0)) / 1e6, 6)
    return out


def critical_path(events) -> list:
    """The longest-child chain under the longest top-level span of ONE
    request's events: [{name, dur_s, self_s, args}] root-first.
    ``self_s`` = dur minus direct children — a hop with high self time
    is where the wall went; a hop whose children cover it is just a
    container."""
    children = {}
    for ev in events:
        children.setdefault(ev.get("parent") or 0, []).append(ev)
    tops = children.get(0, [])
    if not tops:
        return []
    path = []
    node = max(tops, key=lambda e: float(e.get("dur", 0.0)))
    while node is not None:
        kids = children.get(node.get("id"), [])
        dur = float(node.get("dur", 0.0)) / 1e6
        covered = sum(float(k.get("dur", 0.0)) for k in kids) / 1e6
        path.append({"name": node.get("name", "?"),
                     "cat": node.get("cat", "?"),
                     "dur_s": round(dur, 6),
                     "self_s": round(max(0.0, dur - covered), 6),
                     "args": node.get("args") or {}})
        node = max(kids, key=lambda e: float(e.get("dur", 0.0))) \
            if kids else None
    return path


def trace_profile(events, tid: str) -> dict:
    """One request's offline cost profile: roll-up + per-op aggregate +
    critical path (the file-based twin of ``GET /v1/jobs/<id>/profile``)."""
    from gpu_mapreduce_tpu.obs import aggregate_ops
    mine = [e for e in events if e.get("trace") == tid]
    rollup = {k: 0 for k in _BYTE_ARGS}
    dispatches = 0
    for ev in mine:
        args = ev.get("args") or {}
        # roll up from TOP-LEVEL spans only: a child's delta is already
        # inside its parent's (the tracer snapshots per span)
        if not ev.get("parent"):
            for k in _BYTE_ARGS:
                rollup[k] += int(args.get(k, 0) or 0)
            dispatches += int(args.get("dispatches", 0) or 0)
    idx = trace_index(mine).get(tid, {})
    return {"trace_id": tid,
            "spans": len(mine),
            "wall_s": idx.get("wall_s", 0.0),
            "dispatches": dispatches,
            **rollup,
            "ops": aggregate_ops(mine),
            "critical_path": critical_path(mine)}


def trace_report(events, tid: str) -> str:
    from gpu_mapreduce_tpu.obs import per_op_table
    prof = trace_profile(events, tid)
    mine = [e for e in events if e.get("trace") == tid]
    lines = [f"trace {tid}: {prof['spans']} spans, "
             f"{prof['wall_s']:.4f}s wall, "
             f"{prof['dispatches']} dispatches, "
             f"{prof['shuffle_sent_bytes'] / (1 << 20):.3g} Mb sent "
             f"(+{prof['shuffle_pad_bytes'] / (1 << 20):.3g} Mb pad), "
             f"{prof['spill_write_bytes'] / (1 << 20):.3g} Mb spilled",
             "", per_op_table(mine), "", "critical path:"]
    for i, hop in enumerate(prof["critical_path"]):
        lines.append(f"  {'  ' * i}{hop['name']}  "
                     f"{hop['dur_s']:.4f}s (self {hop['self_s']:.4f}s)")
    if not prof["critical_path"]:
        lines.append("  (no spans for this trace id)")
    return "\n".join(lines)


def main(argv) -> int:
    if not argv or argv[0] in ("-h", "--help"):
        print(__doc__.strip())
        return 0 if argv else 1
    path = argv[0]
    chrome = None
    cat = None
    trace = None
    list_traces = False
    as_json = False
    i = 1
    while i < len(argv):
        if argv[i] in ("--chrome", "--cat", "--trace"):
            if i + 1 >= len(argv):
                print(f"{argv[i]} needs a value", file=sys.stderr)
                return 1
            if argv[i] == "--chrome":
                chrome = argv[i + 1]
            elif argv[i] == "--trace":
                trace = argv[i + 1]
            else:
                cat = argv[i + 1]
            i += 2
        elif argv[i] == "--traces":
            list_traces = True
            i += 1
        elif argv[i] == "--json":
            as_json = True
            i += 1
        else:
            print(f"unknown argument: {argv[i]}", file=sys.stderr)
            return 1
    from gpu_mapreduce_tpu.obs import (aggregate_ops, per_op_table,
                                       read_jsonl, write_chrome_trace)
    rundir = path if os.path.isdir(path) else None
    if rundir is not None:
        events, nshards = read_trace_dir(rundir)
        if not nshards:
            print(f"no trace-r*.jsonl shards under {rundir}",
                  file=sys.stderr)
            return 1
    else:
        events = read_jsonl(path)
    if cat:
        events = [e for e in events if e.get("cat") == cat]
    if list_traces:
        idx = trace_index(events)
        if as_json:
            print(json.dumps(idx, indent=2))
        else:
            for tid in sorted(idx, key=lambda t: -idx[t]["wall_s"]):
                r = idx[tid]
                print(f"{tid}  {r['spans']:6d} spans  "
                      f"{r['top_spans']:4d} top  {r['wall_s']:.4f}s")
            if not idx:
                print("(no trace ids in this file)")
        return 0
    if trace is not None:
        if as_json:
            prof = trace_profile(events, trace)
            if rundir is not None:
                mine = [e for e in events if e.get("trace") == trace]
                prof["ranks"] = rank_timeline(mine)
                prof["sync_points"] = sync_alignment(rundir)
            print(json.dumps(prof, indent=2))
        else:
            print(trace_report(events, trace))
            if rundir is not None:
                mine = [e for e in events if e.get("trace") == trace]
                print(dist_report(mine, rundir))
        return 0
    if as_json:
        print(json.dumps(aggregate_ops(events), indent=2))
    else:
        print(per_op_table(events))
        if rundir is not None:
            print(dist_report(events, rundir))
    if chrome:
        n = write_chrome_trace(chrome, events)
        print(f"\nwrote {n} events -> {chrome}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
