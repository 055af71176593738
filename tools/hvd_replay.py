"""hvd_replay: reconstruct a run from its on-disk history WAL.

Reads the segments ``horovod_tpu/utils/history.py`` leaves under
``HVD_HISTORY_DIR`` — delta-encoded registry snapshots, the exact
captured event stream, the rank-0 run manifest, and any
``incident-*.json`` files the alert plane wrote — and answers the
question live tooling cannot: *what did this run look like while it
was degrading*, after the process is gone and no flight dump was ever
solicited.

Modes (composable; default is the timeline report):

* report — run span, manifest provenance, per-metric family summary
  (first/last values, deltas for counters), alert lifecycle, incident
  index.
* ``--metric NAME [--labels k=v,...]`` — print the full time series.
* ``--grep REGEX`` — grep the reconstructed event stream (matches the
  rendered JSON, so field values match too).
* ``--window START:END`` — clamp events/series to a unix-seconds
  window (either side blank = open).
* ``--trace out.json`` — Perfetto/Chrome counter-track export: one
  ``ph:"C"`` track per metric family (gauges and counter rates), plus
  instant events; load in ui.perfetto.dev next to an hvd_slo slot
  trace to line resource curves up under request lanes.
* ``--diff OTHER_DIR`` — compare two runs: manifest provenance
  field-by-field (git sha, device kind/count, mesh, config
  fingerprint — the block of utils/provenance.py) plus
  headline counter end-values side by side.
* ``--incidents [--incident PATH]`` — index or pretty-read incident
  files.
* ``--selftest`` — synthesize a run (including a torn segment tail
  and an incident), reconstruct it, and assert every mode works.

Usage:
    python tools/hvd_replay.py [--dir DIR] [--rank N] [...]

Runbook: docs/alerts.md.
"""

import argparse
import glob
import json
import os
import re
import sys

try:
    from horovod_tpu.utils import history as hvd_history
    from horovod_tpu.utils import provenance as hvd_provenance
except ImportError:  # run straight from a checkout: tools/ is no package
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from horovod_tpu.utils import history as hvd_history
    from horovod_tpu.utils import provenance as hvd_provenance


# -- loading ----------------------------------------------------------------

def load_run(dirpath, rank=0):
    """-> dict with records, torn count, events, missed, manifest,
    incidents (paths) for one rank's WAL."""
    records, torn = hvd_history.read_records(dirpath, rank)
    events, missed = hvd_history.read_events(records)
    return {
        "dir": dirpath,
        "rank": rank,
        "records": records,
        "torn": torn,
        "events": events,
        "missed": missed,
        "manifest": hvd_history.load_manifest(dirpath),
        "incidents": sorted(glob.glob(
            os.path.join(dirpath, "incident-*.json"))),
    }


def _window_us(spec):
    """'START:END' in unix seconds -> (lo_us, hi_us), None = open."""
    if not spec:
        return None, None
    lo, _, hi = spec.partition(":")
    lo_us = int(float(lo) * 1e6) if lo else None
    hi_us = int(float(hi) * 1e6) if hi else None
    return lo_us, hi_us


def _in_window(epoch_us, lo_us, hi_us):
    if lo_us is not None and epoch_us < lo_us:
        return False
    if hi_us is not None and epoch_us > hi_us:
        return False
    return True


def _parse_labels(spec):
    if not spec:
        return None
    out = {}
    for pair in spec.split(","):
        k, _, v = pair.partition("=")
        out[k.strip()] = v.strip()
    return out


# -- report -----------------------------------------------------------------

def _fmt_ts(epoch_us):
    if not epoch_us:
        return "?"
    import datetime
    return datetime.datetime.fromtimestamp(
        epoch_us / 1e6).strftime("%Y-%m-%d %H:%M:%S")


def render_report(run, window=None):
    lo_us, hi_us = _window_us(window)
    lines = []
    recs = run["records"]
    lines.append(f"hvd_replay: {run['dir']} (rank {run['rank']})")
    man = run["manifest"]
    if man:
        prov = man.get("provenance", {})
        bits = [f"run_id={man.get('run_id')}"]
        for key in ("git_sha", "device_kind", "device_count", "mesh",
                    "config_fingerprint", "label"):
            if prov.get(key) is not None:
                bits.append(f"{key}={prov[key]}")
        lines.append("  manifest: " + " ".join(str(b) for b in bits))
    if not recs:
        lines.append("  (no history records)")
        return "\n".join(lines)
    lines.append(
        f"  span: {_fmt_ts(recs[0].get('epoch_us'))} .. "
        f"{_fmt_ts(recs[-1].get('epoch_us'))}  "
        f"({len(recs)} records, {run['torn']} torn, "
        f"{len(run['events'])} events, {run['missed']} missed)")
    # per-family first/last summary off the rematerialized states
    states = list(hvd_history.iter_states(recs))
    first, last = states[0]["metrics"], states[-1]["metrics"]

    def _total(state, name):
        entry = state.get(name)
        if entry is None:
            return None
        tot = 0.0
        for v in entry.get("values", ()):
            tot += v["sum"] if "counts" in v else v.get("value", 0.0)
        return tot

    lines.append("  metrics:")
    for name in sorted(last):
        kind = last[name].get("type")
        a, b = _total(first, name), _total(last, name)
        if kind == "counter":
            delta = (b or 0.0) - (a or 0.0)
            lines.append(f"    {name:<44} {b:>14.6g}  (+{delta:.6g})")
        elif kind == "gauge":
            lines.append(f"    {name:<44} {b:>14.6g}")
        else:
            count = sum(v.get("count", 0)
                        for v in last[name].get("values", ()))
            lines.append(f"    {name:<44} {count:>11.0f} obs")
    alerts = [e for e in run["events"]
              if e.get("event", "").startswith("alert_")
              and _in_window(e.get("epoch_us", 0), lo_us, hi_us)]
    if alerts:
        lines.append("  alerts:")
        for ev in alerts:
            extra = {k: v for k, v in ev.items()
                     if k not in ("event", "ts_us", "epoch_us", "alert",
                                  "severity")}
            lines.append(
                f"    {_fmt_ts(ev.get('epoch_us'))} "
                f"{ev['event'][len('alert_'):]:<9} {ev.get('alert')} "
                f"{extra if extra else ''}")
    if run["incidents"]:
        lines.append("  incidents:")
        for path in run["incidents"]:
            lines.append(f"    {os.path.basename(path)}")
    return "\n".join(lines)


def render_series(run, metric, labels=None, window=None):
    lo_us, hi_us = _window_us(window)
    pts = hvd_history.series(run["records"], metric, labels=labels)
    pts = [(t, v) for t, v in pts if _in_window(t, lo_us, hi_us)]
    lines = [f"{metric} ({len(pts)} points)"]
    for t, v in pts:
        lines.append(f"  {_fmt_ts(t)}  {v:.6g}")
    return "\n".join(lines)


def grep_events(run, pattern, window=None):
    lo_us, hi_us = _window_us(window)
    rx = re.compile(pattern)
    lines = []
    for ev in run["events"]:
        if not _in_window(ev.get("epoch_us", 0), lo_us, hi_us):
            continue
        rendered = json.dumps(ev, sort_keys=True)
        if rx.search(rendered):
            lines.append(f"{_fmt_ts(ev.get('epoch_us'))}  {rendered}")
    return "\n".join(lines) if lines else "(no matching events)"


# -- diff -------------------------------------------------------------------

def render_diff(run_a, run_b):
    """Two runs, lined up by manifest provenance then headline counter
    end-values — the 'what changed between yesterday's run and
    today's' answer."""
    lines = [f"diff: A={run_a['dir']}  B={run_b['dir']}"]
    prov_a = (run_a["manifest"] or {}).get("provenance", {})
    prov_b = (run_b["manifest"] or {}).get("provenance", {})
    lines.append("  provenance:")
    for field, va, vb in hvd_provenance.provenance_diff(prov_a, prov_b):
        marker = " " if va == vb else "!"
        lines.append(f"  {marker} {field:<20} A={va}  B={vb}")

    def _finals(run):
        states = list(hvd_history.iter_states(run["records"]))
        if not states:
            return {}
        out = {}
        for name, entry in states[-1]["metrics"].items():
            tot = 0.0
            for v in entry.get("values", ()):
                tot += v["sum"] if "counts" in v else v.get("value", 0.0)
            out[name] = (entry.get("type"), tot)
        return out

    fa, fb = _finals(run_a), _finals(run_b)
    lines.append("  metrics (final values):")
    for name in sorted(set(fa) | set(fb)):
        ka, va = fa.get(name, (None, None))
        kb, vb = fb.get(name, (None, None))
        sa = "-" if va is None else f"{va:.6g}"
        sb = "-" if vb is None else f"{vb:.6g}"
        marker = " " if sa == sb else "!"
        lines.append(f"  {marker} {name:<44} A={sa:>12}  B={sb:>12}")
    ia, ib = len(run_a["incidents"]), len(run_b["incidents"])
    lines.append(f"  incidents: A={ia}  B={ib}")
    return "\n".join(lines)


# -- incidents --------------------------------------------------------------

def render_incident(path):
    with open(path) as f:
        inc = json.load(f)
    lines = [f"incident: {os.path.basename(path)}"]
    lines.append(f"  alert: {inc.get('alert')} ({inc.get('severity')}) — "
                 f"{inc.get('description')}")
    lines.append(f"  fired: {_fmt_ts(inc.get('fired_epoch_us'))} "
                 f"(window from "
                 f"{_fmt_ts(inc.get('window_start_epoch_us'))})")
    if inc.get("evidence"):
        lines.append(f"  evidence: {inc['evidence']}")
    if inc.get("dominant_phase"):
        lines.append(f"  dominant phase: {inc['dominant_phase']} "
                     f"(phase_ms: {inc.get('phase_ms')})")
    if inc.get("stranded_request_ids"):
        lines.append("  stranded requests: "
                     + ", ".join(inc["stranded_request_ids"]))
    lines.append(f"  correlated: {len(inc.get('request_ids', []))} "
                 f"request ids, {len(inc.get('trace_ids', []))} trace ids, "
                 f"{len(inc.get('events', []))} events, "
                 f"{len(inc.get('history', []))} history records")
    man = inc.get("manifest") or {}
    if man.get("run_id"):
        lines.append(f"  run: {man['run_id']}")
    return "\n".join(lines)


def render_incident_index(run):
    if not run["incidents"]:
        return "(no incidents)"
    lines = []
    for path in run["incidents"]:
        try:
            with open(path) as f:
                inc = json.load(f)
        except (OSError, ValueError):
            lines.append(f"{os.path.basename(path)}  (unreadable)")
            continue
        lines.append(
            f"{os.path.basename(path)}  alert={inc.get('alert')} "
            f"severity={inc.get('severity')} "
            f"fired={_fmt_ts(inc.get('fired_epoch_us'))} "
            f"stranded={len(inc.get('stranded_request_ids', []))}")
    return "\n".join(lines)


# -- Perfetto export --------------------------------------------------------

def chrome_trace(run):
    """Chrome/Perfetto counter tracks: one ``ph:"C"`` track per metric
    family (gauges plot their value, counters their per-interval
    rate), alert/other events as instants on a dedicated thread row."""
    events = []
    pid = run["rank"] or 0
    events.append({"ph": "M", "pid": pid, "name": "process_name",
                   "args": {"name": f"hvd-history rank{pid}"}})
    prev = {}
    prev_ts = None
    for state in hvd_history.iter_states(run["records"]):
        ts = state["epoch_us"]
        for name, entry in state["metrics"].items():
            kind = entry.get("type")
            if kind == "histogram":
                continue
            tot = 0.0
            for v in entry.get("values", ()):
                tot += v.get("value", 0.0)
            if kind == "counter":
                dv = tot - prev.get(name, 0.0)
                dt = (ts - prev_ts) / 1e6 if prev_ts else None
                prev[name] = tot
                if dt is None or dt <= 0:
                    continue
                events.append({"ph": "C", "pid": pid, "ts": ts,
                               "name": f"{name}/s",
                               "args": {"rate": round(dv / dt, 4)}})
            else:
                events.append({"ph": "C", "pid": pid, "ts": ts,
                               "name": name, "args": {"value": tot}})
        prev_ts = ts
    for ev in run["events"]:
        events.append({"ph": "i", "pid": pid, "tid": 1, "s": "t",
                       "ts": ev.get("epoch_us", 0),
                       "name": ev.get("event", "event"),
                       "args": {k: v for k, v in ev.items()
                                if k not in ("ts_us", "epoch_us")}})
    return {"traceEvents": events, "displayTimeUnit": "ms"}


# -- selftest ---------------------------------------------------------------

def selftest():
    """End-to-end: synthesize a degrading run, tear the WAL tail, then
    assert reconstruction, series, grep, incident reading, Perfetto
    export and --diff all work from disk alone."""
    import shutil
    import tempfile

    from horovod_tpu.utils import alerts as hvd_alerts
    from horovod_tpu.utils import metrics as hvd_metrics

    base = tempfile.mkdtemp(prefix="hvd-replay-selftest-")
    failures = []

    def check(cond, what):
        print(f"  {'PASS' if cond else 'FAIL'}: {what}")
        if not cond:
            failures.append(what)

    try:
        runs = {}
        for tag, degrade in (("a", False), ("b", True)):
            d = os.path.join(base, tag)
            reg = hvd_metrics.MetricsRegistry(rank=0)
            writer = hvd_history.HistoryWriter(
                d, rank=0, interval_s=0.01, max_mb=1, registry=reg)
            writer.annotate(mesh={"dp": 2, "tp": 2},
                            label=f"selftest-{tag}")
            mgr = hvd_alerts.AlertManager(
                registry=reg, interval_s=0.0, incident_dir=d,
                history_writer=writer)
            good = reg.counter("hvd_serve_goodput_tokens_total", "")
            bad = reg.counter("hvd_serve_wasted_tokens_total", "",
                              labels=("reason",))
            depth = reg.gauge("hvd_serve_queue_depth", "")
            reg.event("serve_admit", request_id=f"{tag}-stuck")
            t = 0.0
            for i in range(40):
                t += 1.0
                if degrade and i >= 10:
                    good.inc(5)
                    bad.labels(reason="expired").inc(95)
                    depth.set(30)
                    if i == 12:
                        reg.event("serve_retire",
                                  request_id=f"{tag}-r{i}",
                                  outcome="expired", reason="deadline",
                                  phase_ms={"queue_wait": 800.0,
                                            "decode": 100.0},
                                  ttft_s=2.5)
                else:
                    good.inc(100)
                    depth.set(1)
                writer.flush(wait=True)
                mgr.tick(t)
            writer.close()
            runs[tag] = d
        # torn tail on run b: append half a record to the last segment
        segs = sorted(glob.glob(
            os.path.join(runs["b"], "history-rank0-*.jsonl")))
        with open(segs[-1], "a") as f:
            f.write('{"v": 1, "t": "delta", "seq": 9999, "metr')

        run_a, run_b = load_run(runs["a"]), load_run(runs["b"])
        check(run_b["torn"] == 1 and len(run_b["records"]) >= 40,
              "torn tail skipped, records intact")
        report = render_report(run_b)
        check("hvd_serve_wasted_tokens_total" in report
              and "selftest-b" in report, "report renders metrics+manifest")
        pts = hvd_history.series(
            run_b["records"], "hvd_serve_queue_depth")
        check(pts and pts[-1][1] == 30.0, "gauge series reconstructs")
        check("serve_retire" in grep_events(run_b, "deadline"),
              "event grep finds field values")
        check(run_b["incidents"] and not run_a["incidents"],
              "degraded run produced an incident, healthy run none")
        inc_text = render_incident(run_b["incidents"][0])
        check("queue_wait" in inc_text and "b-stuck" in inc_text,
              "incident names dominant phase and stranded request")
        diff = render_diff(run_a, run_b)
        check("label" in diff and "incidents: A=0  B=1" in diff,
              "--diff lines up provenance and incident counts")
        trace = chrome_trace(run_b)
        kinds = {e.get("ph") for e in trace["traceEvents"]}
        check("C" in kinds and "i" in kinds,
              "Perfetto export has counter tracks and instants")
        alerts_seen = {e["event"] for e in run_b["events"]
                       if e.get("event", "").startswith("alert_")}
        check({"alert_pending", "alert_firing"} <= alerts_seen,
              "alert lifecycle events captured in the WAL")
    finally:
        shutil.rmtree(base, ignore_errors=True)
    if failures:
        print(f"selftest: {len(failures)} FAILED")
        return 1
    print("selftest: all checks passed")
    return 0


# -- CLI --------------------------------------------------------------------

def main(argv=None):
    ap = argparse.ArgumentParser(
        prog="hvd_replay", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--dir", default=None,
                    help="history directory (default: HVD_HISTORY_DIR "
                         "resolution)")
    ap.add_argument("--rank", type=int, default=0)
    ap.add_argument("--metric", default=None,
                    help="print one metric's time series")
    ap.add_argument("--labels", default=None,
                    help="k=v,... label filter for --metric")
    ap.add_argument("--grep", default=None,
                    help="regex over the reconstructed event stream")
    ap.add_argument("--window", default=None,
                    help="START:END unix-seconds window (blank = open)")
    ap.add_argument("--diff", default=None, metavar="DIR",
                    help="second run's history dir to compare against")
    ap.add_argument("--trace", default=None, metavar="OUT",
                    help="write a Perfetto counter-track trace")
    ap.add_argument("--incidents", action="store_true",
                    help="index the run's incident files")
    ap.add_argument("--incident", default=None, metavar="PATH",
                    help="pretty-print one incident file")
    ap.add_argument("--json", action="store_true",
                    help="machine output for report/diff modes")
    ap.add_argument("--selftest", action="store_true")
    args = ap.parse_args(argv)

    if args.selftest:
        return selftest()
    if args.incident:
        print(render_incident(args.incident))
        return 0

    dirpath = args.dir or hvd_history.history_dir()
    run = load_run(dirpath, rank=args.rank)
    if not run["records"] and not run["incidents"] and \
            run["manifest"] is None:
        print(f"hvd_replay: no history found under {dirpath}",
              file=sys.stderr)
        return 2

    if args.diff:
        other = load_run(args.diff, rank=args.rank)
        if args.json:
            print(json.dumps({
                "a": {"dir": run["dir"],
                      "manifest": run["manifest"],
                      "incidents": run["incidents"]},
                "b": {"dir": other["dir"],
                      "manifest": other["manifest"],
                      "incidents": other["incidents"]}}, indent=1))
        else:
            print(render_diff(run, other))
        return 0
    if args.incidents:
        print(render_incident_index(run))
        return 0
    if args.metric:
        print(render_series(run, args.metric,
                            labels=_parse_labels(args.labels),
                            window=args.window))
        return 0
    if args.grep:
        print(grep_events(run, args.grep, window=args.window))
        return 0
    if args.trace:
        with open(args.trace, "w") as f:
            json.dump(chrome_trace(run), f)
        print(f"wrote {args.trace} "
              f"({len(run['records'])} records) — open in ui.perfetto.dev")
        return 0
    if args.json:
        states = list(hvd_history.iter_states(run["records"]))
        print(json.dumps({
            "dir": run["dir"], "rank": run["rank"],
            "records": len(run["records"]), "torn": run["torn"],
            "events": len(run["events"]), "missed": run["missed"],
            "manifest": run["manifest"],
            "incidents": run["incidents"],
            "final_metrics": states[-1]["metrics"] if states else {}},
            indent=1))
        return 0
    print(render_report(run, window=args.window))
    return 0


if __name__ == "__main__":
    sys.exit(main())
