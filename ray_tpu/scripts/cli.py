"""ray-tpu CLI: start / stop / status / submit / logs / jobs / timeline.

TPU-native analog of the reference's CLI surface
(/root/reference/python/ray/scripts/scripts.py — `ray start/stop/status/
timeline`; dashboard/modules/job/cli.py — `ray job submit`).

Usage:
    python -m ray_tpu start --head [--port 6380] [--num-cpus 8] [--store-path p]
    python -m ray_tpu start --address host:port      # join as a worker node
    python -m ray_tpu status [--address host:port]
    python -m ray_tpu drain NODE_ID [--no-wait]    # graceful node drain
    python -m ray_tpu submit [--address ...] -- python my_script.py
    python -m ray_tpu jobs [--address ...]
    python -m ray_tpu logs JOB_ID [--address ...]
    python -m ray_tpu stop
    python -m ray_tpu timeline --out trace.json
    python -m ray_tpu metrics [NAME] [--tags k=v] [--since TS] [--watch]
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import time

_STATE_DIR = os.path.expanduser("~/.ray_tpu")
_ADDR_FILE = os.path.join(_STATE_DIR, "address")
_PID_FILE = os.path.join(_STATE_DIR, "head.pid")


def _write_state(address: str, pid: int) -> None:
    os.makedirs(_STATE_DIR, exist_ok=True)
    with open(_ADDR_FILE, "w") as f:
        f.write(address)
    with open(_PID_FILE, "w") as f:
        f.write(str(pid))


def _read_address(cli_value: str | None) -> str:
    if cli_value:
        return cli_value
    env = os.environ.get("RAY_TPU_ADDRESS")
    if env:
        return env
    if os.path.exists(_ADDR_FILE):
        with open(_ADDR_FILE) as f:
            return f.read().strip()
    raise SystemExit("no cluster address: pass --address, set "
                     "RAY_TPU_ADDRESS, or `ray-tpu start --head` first")


# ---- head/worker node daemons ---------------------------------------------

def _run_head_daemon(args) -> None:
    """The long-lived head process (GCS+raylet analog in-proc)."""
    from ray_tpu.core.control_plane import ControlPlane
    from ray_tpu.core.node_agent import NodeAgent

    cp = ControlPlane(port=args.port, store_path=args.store_path or None)
    res = {"CPU": float(args.num_cpus or (os.cpu_count() or 1))}
    agent = NodeAgent(cp.addr, resources=res)
    addr = f"{cp.addr[0]}:{cp.addr[1]}"
    dashboard = None
    if getattr(args, "dashboard_port", -1) >= 0:
        import ray_tpu
        ray_tpu.init(address=addr)
        from ray_tpu.dashboard import start_dashboard
        dashboard = start_dashboard(port=args.dashboard_port)
        print(f"dashboard at http://127.0.0.1:{dashboard.port}", flush=True)
    print(f"ray_tpu head up at {addr}", flush=True)
    stop = []
    signal.signal(signal.SIGTERM, lambda *_: stop.append(1))
    while not stop:
        time.sleep(0.5)
    if dashboard is not None:
        dashboard.stop()
    agent.stop()
    cp.stop()


def _parse_labels(spec: str | None) -> dict:
    out = {}
    for item in filter(None, (spec or "").split(",")):
        k, _, v = item.partition("=")
        out[k] = v
    return out


def _run_node_daemon(args) -> None:
    """A long-lived worker-node agent joining an existing cluster."""
    from ray_tpu.core.node_agent import NodeAgent

    host, port = _read_address(args.address).rsplit(":", 1)
    res = {"CPU": float(args.num_cpus or (os.cpu_count() or 1))}
    agent = NodeAgent((host, int(port)), resources=res,
                      labels=_parse_labels(getattr(args, "labels", None)))
    print(f"ray_tpu node joined {host}:{port} as {agent.node_id.hex()[:8]}",
          flush=True)
    stop = []
    signal.signal(signal.SIGTERM, lambda *_: stop.append(1))
    while not stop:
        time.sleep(0.5)
    agent.stop()


def cmd_start(args) -> None:
    if args.block:
        if args.head:
            _run_head_daemon(args)
        else:
            _run_node_daemon(args)
        return
    # detach: re-exec ourselves with --block in a daemonized subprocess
    cmd = [sys.executable, "-m", "ray_tpu", "start", "--block"]
    if args.head:
        cmd += ["--head", "--port", str(args.port),
                "--dashboard-port", str(args.dashboard_port)]
        if args.store_path:
            cmd += ["--store-path", args.store_path]
    else:
        cmd += ["--address", _read_address(args.address)]
        if args.labels:
            cmd += ["--labels", args.labels]
    if args.num_cpus:
        cmd += ["--num-cpus", str(args.num_cpus)]
    os.makedirs(_STATE_DIR, exist_ok=True)
    log = open(os.path.join(_STATE_DIR, "head.log" if args.head
                            else f"node-{os.getpid()}.log"), "ab")
    proc = subprocess.Popen(cmd, stdout=log, stderr=subprocess.STDOUT,
                            start_new_session=True)
    if args.head:
        address = f"127.0.0.1:{args.port}"
        _write_state(address, proc.pid)
        # wait for the control plane to accept connections
        from ray_tpu.core.rpc import RpcClient
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            try:
                RpcClient(("127.0.0.1", args.port), name="probe").call(
                    "ping", None, timeout=2.0)
                print(f"started head at {address} (pid {proc.pid})")
                print(f"connect with: ray_tpu.init(address='{address}')")
                return
            except Exception:  # noqa: BLE001
                time.sleep(0.2)
        raise SystemExit("head failed to start; see ~/.ray_tpu/head.log")
    print(f"started worker node (pid {proc.pid})")


def cmd_stop(args) -> None:
    stopped = False
    if os.path.exists(_PID_FILE):
        with open(_PID_FILE) as f:
            pid = int(f.read().strip())
        # the head was started with start_new_session=True, so its process
        # group holds exactly this cluster (head + its spawned workers);
        # killing the group never touches other clusters on the machine
        def _signal(sig):
            try:
                os.killpg(pid, sig)
            except (ProcessLookupError, PermissionError):
                try:
                    os.kill(pid, sig)
                except ProcessLookupError:
                    raise
        try:
            _signal(signal.SIGTERM)
            stopped = True
            # wait for exit so a follow-up `start` can rebind the ports
            deadline = time.time() + 10.0
            while time.time() < deadline:
                try:
                    os.kill(pid, 0)
                except ProcessLookupError:
                    break
                time.sleep(0.1)
            else:
                try:
                    _signal(signal.SIGKILL)
                except ProcessLookupError:
                    pass
            print(f"stopped head (pid {pid})")
        except ProcessLookupError:
            pass
        os.remove(_PID_FILE)
    if os.path.exists(_ADDR_FILE):
        os.remove(_ADDR_FILE)
    if getattr(args, "force", False):
        # explicit opt-in only: this reaps EVERY ray_tpu worker on the
        # machine, including other live clusters'
        subprocess.run(["pkill", "-f", "ray_tpu.core.worker_main"],
                       check=False)
        print("killed all ray_tpu workers on this machine (--force)")
    elif not stopped:
        print("no head pidfile; nothing stopped (use --force to reap "
              "stray workers)")


def cmd_status(args) -> None:
    import ray_tpu
    ray_tpu.init(address=_read_address(args.address))
    from ray_tpu.util import state

    nodes = ray_tpu.nodes()
    print(f"nodes: {len(nodes)}")
    for n in nodes:
        # the CP-side state machine (ALIVE/DRAINING/DRAINED/DEAD); older
        # CPs only report the alive bit
        st = n.get("state") or ("ALIVE" if n["alive"] else "DEAD")
        progress = ""
        if st == "DRAINING" and n.get("draining_since"):
            from ray_tpu.core.config import get_config
            elapsed = time.time() - n["draining_since"]
            progress = (f" (draining {elapsed:.0f}s/"
                        f"{get_config().drain_deadline_s:.0f}s)")
        print(f"  {n['node_id'].hex()[:8]} {st}{progress} at {n['addr']} "
              f"resources={n['resources']} available={n['available']}")
    actors = state.list_actors()
    by_state: dict[str, int] = {}
    for a in actors:
        by_state[a["state"]] = by_state.get(a["state"], 0) + 1
    print(f"actors: {by_state or 0}")
    pgs = state.list_placement_groups()
    print(f"placement groups: {len(pgs)}")

    # serve prefix-affinity routing (ISSUE 10): router counters from the
    # CP time-series store; silent until a router has reported
    def _counter_total(name: str):
        try:
            res = state.query_metrics(name)
            if not res or not res.get("series"):
                return None
            return sum(s["points"][-1][1] for s in res["series"])
        except Exception:  # noqa: BLE001 — metrics are best-effort
            return None

    hits = _counter_total("ray_tpu_serve_router_affinity_hits_total")
    if hits is not None:
        spill = _counter_total(
            "ray_tpu_serve_router_affinity_spillovers_total") or 0
        stale = _counter_total(
            "ray_tpu_serve_router_affinity_stale_fallbacks_total") or 0
        print(f"serve affinity: hits={hits:.0f} spillovers={spill:.0f} "
              f"stale_fallbacks={stale:.0f}")
    ray_tpu.shutdown()


def cmd_drain(args) -> None:
    """Gracefully drain a node instead of killing it: stop new leases, let
    in-flight work finish, migrate primary objects, then deregister."""
    import ray_tpu
    from ray_tpu.util import state

    ray_tpu.init(address=_read_address(args.address))
    try:
        out = state.drain_node(args.node_id, wait=not args.no_wait,
                               reason="ray-tpu drain CLI")
    except ValueError as e:
        raise SystemExit(str(e))
    print(f"drain {args.node_id}: state={out.get('state')}")
    ray_tpu.shutdown()
    if not out.get("ok"):
        raise SystemExit(out.get("error") or "drain failed")


def cmd_submit(args) -> None:
    import ray_tpu
    from ray_tpu.job import JobSubmissionClient

    ray_tpu.init(address=_read_address(args.address))
    client = JobSubmissionClient()
    entrypoint = " ".join(args.entrypoint)
    job_id = client.submit_job(entrypoint=entrypoint,
                               working_dir=args.working_dir)
    print(f"submitted {job_id}: {entrypoint}")
    if args.no_wait:
        return
    status = client.wait_until_finished(job_id, timeout=args.timeout)
    print(f"status: {status.value}")
    print("---- logs ----")
    print(client.get_job_logs(job_id))
    if status.value != "SUCCEEDED":
        raise SystemExit(1)


def cmd_jobs(args) -> None:
    import ray_tpu
    from ray_tpu.job import JobSubmissionClient

    ray_tpu.init(address=_read_address(args.address))
    for rec in JobSubmissionClient().list_jobs():
        print(json.dumps(rec))


def cmd_logs(args) -> None:
    import ray_tpu
    from ray_tpu.job import JobSubmissionClient

    ray_tpu.init(address=_read_address(args.address))
    print(JobSubmissionClient().get_job_logs(args.job_id, tail=args.tail))


def cmd_timeline(args) -> None:
    import ray_tpu
    from ray_tpu.util import state

    ray_tpu.init(address=_read_address(args.address))
    out = args.out or f"timeline-{int(time.time())}.json"
    state.timeline(filename=out)
    print(f"wrote chrome trace to {out} (open in chrome://tracing)")


def cmd_trace(args) -> None:
    import ray_tpu
    from ray_tpu.util import state

    ray_tpu.init(address=_read_address(args.address))
    if not args.trace_id:
        # no id: list what the trace store holds
        for meta in state.list_traces(limit=args.limit):
            print(json.dumps(meta))
        return
    if args.out:
        state.trace_timeline(args.trace_id, filename=args.out,
                             fmt=args.format)
        hint = (" (open in chrome://tracing)" if args.format == "chrome"
                else "")
        print(f"wrote {args.format} trace to {args.out}{hint}")
    else:
        print(state.trace_timeline(args.trace_id, fmt=args.format))


def cmd_profile(args) -> None:
    import ray_tpu
    from ray_tpu.util import state

    ray_tpu.init(address=_read_address(args.address))
    if args.list:
        for art in state.list_profile_artifacts():
            print(json.dumps(art))
        return
    if args.memory:
        out = state.save_device_memory_profile(node_id=args.node,
                                               path=args.logdir)
        print(json.dumps(out, indent=2))
        return
    print(f"capturing XPlane trace for {args.duration:g}s "
          f"({'node ' + args.node if args.node else 'all nodes'})…",
          file=sys.stderr)
    out = state.capture_xprof(node_id=args.node, duration=args.duration,
                              logdir=args.logdir)
    arts = out.get("artifacts") or []
    for art in arts:
        print(json.dumps(art))
    if arts:
        print(f"{len(arts)} capture(s); inspect with "
              f"`tensorboard --logdir {arts[0]['logdir']}` (Profile tab)",
              file=sys.stderr)
    else:
        print("no captures produced:", file=sys.stderr)
        print(json.dumps(out, indent=2), file=sys.stderr)
        raise SystemExit(1)


def cmd_kvtier(args) -> None:
    import ray_tpu
    from ray_tpu.util import state

    ray_tpu.init(address=_read_address(args.address))
    if args.gc:
        out = state.kv_tier_gc()
        print(f"gc dropped {out.get('dropped', 0)} expired entries",
              file=sys.stderr)
    res = state.list_kv_tier()
    entries = res.get("entries") or []
    if args.json:
        print(json.dumps(res, indent=2))
        return
    # per-entry rows, then totals per tier/node + CP hit counters
    by_tier: dict[str, dict] = {}
    by_node: dict[str, int] = {}
    for e in entries:
        t = by_tier.setdefault(e.get("tier", "?"),
                               {"entries": 0, "bytes": 0, "raw": 0})
        t["entries"] += 1
        t["bytes"] += int(e.get("nbytes") or 0)
        # pre-codec size; raw-format entries (codec "none", pre-codec
        # publishers) carry no "raw" field — stored == raw there
        t["raw"] += int(e.get("raw") or e.get("nbytes") or 0)
        node = (e.get("node") or "?")[:8]
        by_node[node] = by_node.get(node, 0) + 1
        print(json.dumps({
            "digest": (e.get("digest") or "")[:16],
            "tier": e.get("tier"), "node": node,
            "owner": (e.get("owner") or "")[:8],
            "tokens": e.get("tokens"), "nbytes": e.get("nbytes"),
            "raw": e.get("raw"),
            "age_s": round(time.time() - e["ts"], 1)
            if e.get("ts") else None}))
    print(f"# {len(entries)} indexed pages", file=sys.stderr)
    for tier, agg in sorted(by_tier.items()):
        ratio = (agg["raw"] / agg["bytes"]) if agg["bytes"] else 0.0
        print(f"#   tier={tier}: {agg['entries']} entries "
              f"{agg['bytes']} bytes stored / {agg['raw']} raw "
              f"(codec ratio {ratio:.2f}x => holds {ratio:.2f}x the "
              f"prefix tokens per byte cap)", file=sys.stderr)
    for node, n in sorted(by_node.items()):
        print(f"#   node={node}: {n} entries", file=sys.stderr)
    c = res.get("counters") or {}
    print(f"# match_calls={c.get('match_calls', 0)} "
          f"hits={c.get('hits', 0)} misses={c.get('misses', 0)} "
          f"hit_pages={c.get('hit_pages', 0)}", file=sys.stderr)


def cmd_slo(args) -> None:
    """Tail-latency attribution (ISSUE 12): per-stage breakdown table,
    exemplar listing, one-exemplar waterfall, per-replica skew."""
    import ray_tpu
    from ray_tpu.util import state

    ray_tpu.init(address=_read_address(args.address))

    if args.exemplar:
        rec = state.get_slo_exemplar(args.exemplar)
        if rec is None:
            print(f"no exemplar matching {args.exemplar!r}", file=sys.stderr)
            raise SystemExit(1)
        if args.json:
            print(json.dumps(rec, indent=2))
            return
        from ray_tpu.observability import attribution, tracing
        spans = attribution.stages_to_spans(rec)
        if args.out:
            with open(args.out, "w") as f:
                json.dump(tracing.to_chrome_trace(spans), f)
            print(f"chrome trace written to {args.out} "
                  f"(load in chrome://tracing or Perfetto)", file=sys.stderr)
            return
        _print_exemplar_waterfall(rec, spans)
        return

    if args.exemplars:
        rows = state.list_slo_exemplars(limit=args.limit, kind=args.kind)
        if args.json:
            print(json.dumps(rows, indent=2))
            return
        for r in rows:
            print(json.dumps(r))
        print(f"# {len(rows)} exemplar(s); `ray-tpu slo --exemplar <id>` "
              f"renders one waterfall", file=sys.stderr)
        return

    report = state.slo_report(deployment=args.deployment)
    if args.json:
        print(json.dumps(report, indent=2))
        return
    print(f"# {report.get('count', 0)} exemplar(s), "
          f"{report.get('violations', 0)} SLO violation(s)",
          file=sys.stderr)
    stage_ms = report.get("stage_ms") or {}
    if stage_ms:
        print(f"{'stage':<10} {'p50_ms':>10} {'p95_ms':>10} "
              f"{'p99_ms':>10} {'count':>7}")
        for stage, row in stage_ms.items():
            print(f"{stage:<10} {row['p50']:>10.2f} {row['p95']:>10.2f} "
                  f"{row['p99']:>10.2f} {row['count']:>7}")
    dom = report.get("dominant_stage") or {}
    if dom:
        ranked = sorted(dom.items(), key=lambda kv: kv[1], reverse=True)
        print("# dominant stage of tail requests: "
              + ", ".join(f"{s}={n}" for s, n in ranked), file=sys.stderr)
    if args.replica_skew or not stage_ms:
        skew = report.get("replica_skew") or {}
        if skew:
            print(f"{'replica':<14} {'count':>6} {'qwait_p50':>10} "
                  f"{'qwait_p95':>10} {'hit_share':>10} {'prefilled':>10}")
            for rep, row in sorted(skew.items()):
                print(f"{rep:<14} {row['count']:>6} "
                      f"{row['queue_wait_p50_ms']:>10.2f} "
                      f"{row['queue_wait_p95_ms']:>10.2f} "
                      f"{row['affinity_hit_share']:>10.2f} "
                      f"{row['prefilled_tokens']:>10}")


def _print_exemplar_waterfall(rec: dict, spans: list) -> None:
    """Text waterfall of one exemplar's stage timeline (the PR 1 trace
    span shapes, so the bar math matches `ray-tpu trace`)."""
    stages = [s for s in spans if s.get("parent_id")]
    if not stages:
        print("(no stages recorded)", file=sys.stderr)
        return
    t_min = min(s["start"] for s in stages)
    t_max = max(s["end"] for s in stages)
    span_total = max(t_max - t_min, 1e-9)
    width = 40
    head = (f"request {rec.get('request_id')} kind={rec.get('kind')} "
            f"violated={','.join(rec.get('violated') or []) or '-'} "
            f"replica={rec.get('replica') or '-'} "
            f"ttft_ms={rec.get('ttft_ms')} e2e_ms={rec.get('e2e_ms')}")
    print(f"# {head}", file=sys.stderr)
    for s in stages:
        off = int((s["start"] - t_min) / span_total * width)
        ln = max(1, int((s["end"] - s["start"]) / span_total * width))
        bar = " " * off + "█" * min(ln, width - off)
        dur_ms = (s["end"] - s["start"]) * 1e3
        attrs = s.get("attrs") or {}
        note = " ".join(f"{k}={v}" for k, v in attrs.items())
        print(f"{s['name'][6:]:<10} |{bar:<{width}}| "
              f"{dur_ms:>9.2f} ms  {note}")


def _fmt_hms(ts: float) -> str:
    import datetime
    return datetime.datetime.fromtimestamp(
        float(ts or 0.0)).strftime("%H:%M:%S.%f")[:-3]


def _fmt_event_line(ev: dict) -> str:
    ent = " ".join(f"{k}={ev[k]}" for k in
                   ("node", "deployment", "replica", "request_id")
                   if ev.get(k))
    attrs = ev.get("attrs") or {}
    note = " ".join(f"{k}={v}" for k, v in attrs.items())
    reason = ev.get("reason") or ""
    tail = " | ".join(x for x in (ent, reason, note) if x)
    return (f"{_fmt_hms(ev.get('ts'))} {ev.get('severity', 'INFO'):<7} "
            f"{ev.get('kind', '?'):<20} {tail}")


def cmd_events(args) -> None:
    """Flight recorder (ISSUE 19): tail the cluster event journal, or
    render one postmortem incident timeline joining events + metric
    spikes + SLO exemplars."""
    import ray_tpu
    from ray_tpu.util import state

    ray_tpu.init(address=_read_address(args.address))

    if args.postmortem is not None:
        pm = state.events_postmortem(window_s=args.postmortem)
        if args.json:
            print(json.dumps(pm, indent=2))
            return
        items = pm.get("items") or []
        print(f"# postmortem window {pm.get('window_s')}s "
              f"({_fmt_hms(pm.get('since'))} → {_fmt_hms(pm.get('until'))})"
              f", {len(items)} item(s)", file=sys.stderr)
        for it in items:
            typ = it.get("type")
            if typ == "event":
                print("EV  " + _fmt_event_line(it))
            elif typ == "exemplar":
                print(f"SLO {_fmt_hms(it.get('ts'))} VIOLATION "
                      f"request_id={it.get('request_id')} "
                      f"deployment={it.get('deployment') or '-'} "
                      f"violated={','.join(it.get('violated') or [])} "
                      f"ttft_ms={it.get('ttft_ms')} "
                      f"e2e_ms={it.get('e2e_ms')}")
            elif typ == "metric":
                tags = ",".join(it.get("tags") or [])
                print(f"MET {_fmt_hms(it.get('ts'))} peak    "
                      f"{it.get('name')}"
                      f"{('{' + tags + '}') if tags else ''} "
                      f"first={it.get('first')} peak={it.get('peak')} "
                      f"last={it.get('last')} "
                      f"points={it.get('points')} "
                      f"source={it.get('source')}")
        return

    since = (time.time() - args.since) if args.since else None
    rows = state.list_events(kind=args.kind, severity=args.severity,
                             entity=args.entity, since=since,
                             limit=args.tail)
    if args.json:
        print(json.dumps(rows, indent=2))
        return
    for ev in reversed(rows):  # store answers newest first; print in order
        print(_fmt_event_line(ev))
    print(f"# {len(rows)} event(s); `ray-tpu events --postmortem 300` "
          f"joins the last 5 minutes against metrics + SLO exemplars",
          file=sys.stderr)


def _parse_tags(spec: str | None) -> dict | None:
    tags = _parse_labels(spec)
    return tags or None


def cmd_metrics(args) -> None:
    import ray_tpu
    from ray_tpu.util import state

    ray_tpu.init(address=_read_address(args.address))
    if not args.name:
        # no name: catalogue of stored series
        for row in state.list_metric_series(prefix=args.prefix):
            print(json.dumps(row))
        return

    def show():
        res = state.query_metrics(args.name, tags=_parse_tags(args.tags),
                                  since=args.since, until=args.until)
        if res is None:
            print(f"no stored metric named {args.name!r}", file=sys.stderr)
            return
        for ser in res["series"]:
            tags = dict(zip(res["tag_keys"], ser["tags"]))
            print(f"# source={ser['source']} tags={tags}")
            for ts, val in ser["points"][-args.limit:]:
                print(json.dumps({"ts": ts, "value": val}))
        if res.get("merged"):
            from ray_tpu.util.metrics import percentiles_from_buckets
            qs = percentiles_from_buckets(res["boundaries"],
                                          res["merged"]["buckets"])
            print(f"# merged count={res['merged']['count']} "
                  f"sum={res['merged']['sum']:.6g} "
                  + " ".join(f"p{round(q * 100)}="
                             f"{'n/a' if v is None else format(v, '.6g')}"
                             for q, v in qs.items()))

    show()
    while args.watch:
        time.sleep(args.interval)
        print("---")
        show()


def cmd_lint(args) -> None:
    from ray_tpu.analysis.cli import lint

    rc = lint(paths=args.paths or None, json_out=args.json,
              write_baseline=args.baseline,
              baseline_file=args.baseline_file,
              include_tests=args.tests)
    raise SystemExit(rc)


def main(argv=None) -> None:
    p = argparse.ArgumentParser(prog="ray-tpu")
    sub = p.add_subparsers(dest="cmd", required=True)

    sp = sub.add_parser("start", help="start a head or worker node")
    sp.add_argument("--head", action="store_true")
    sp.add_argument("--address", default=None)
    sp.add_argument("--port", type=int, default=6380)
    sp.add_argument("--num-cpus", type=float, default=None)
    sp.add_argument("--store-path", default=None,
                    help="sqlite path for control-plane fault tolerance")
    sp.add_argument("--dashboard-port", type=int, default=8265,
                    help="-1 disables the dashboard")
    sp.add_argument("--labels", default=None,
                    help="node labels, k=v[,k2=v2] (worker nodes)")
    sp.add_argument("--block", action="store_true",
                    help="run in the foreground")
    sp.set_defaults(fn=cmd_start)

    sp = sub.add_parser("stop", help="stop the local head + workers")
    sp.add_argument("--force", action="store_true",
                    help="also pkill every ray_tpu worker on this machine")
    sp.set_defaults(fn=cmd_stop)

    sp = sub.add_parser("status", help="cluster summary")
    sp.add_argument("--address", default=None)
    sp.set_defaults(fn=cmd_status)

    sp = sub.add_parser(
        "drain", help="gracefully drain a node (in-flight work finishes, "
                      "objects migrate) instead of killing it")
    sp.add_argument("node_id", help="node id (hex prefix ok)")
    sp.add_argument("--address", default=None)
    sp.add_argument("--no-wait", action="store_true",
                    help="request the drain and return immediately")
    sp.set_defaults(fn=cmd_drain)

    sp = sub.add_parser("submit", help="run an entrypoint as a managed job")
    sp.add_argument("--address", default=None)
    sp.add_argument("--working-dir", default=None)
    sp.add_argument("--no-wait", action="store_true")
    sp.add_argument("--timeout", type=float, default=3600.0)
    sp.add_argument("entrypoint", nargs=argparse.REMAINDER,
                    help="-- python my_script.py ...")
    sp.set_defaults(fn=cmd_submit)

    sp = sub.add_parser("jobs", help="list jobs")
    sp.add_argument("--address", default=None)
    sp.set_defaults(fn=cmd_jobs)

    sp = sub.add_parser("logs", help="print a job's driver log")
    sp.add_argument("job_id")
    sp.add_argument("--address", default=None)
    sp.add_argument("--tail", type=int, default=1000)
    sp.set_defaults(fn=cmd_logs)

    sp = sub.add_parser("timeline", help="dump a chrome trace of task events")
    sp.add_argument("--address", default=None)
    sp.add_argument("--out", default=None)
    sp.set_defaults(fn=cmd_timeline)

    sp = sub.add_parser(
        "trace", help="list traces, or export one by id (chrome/otlp json)")
    sp.add_argument("trace_id", nargs="?", default=None,
                    help="trace id (prefix ok); omit to list traces")
    sp.add_argument("--address", default=None)
    sp.add_argument("--out", default=None,
                    help="output file (default: print to stdout)")
    sp.add_argument("--format", choices=("chrome", "otlp"), default="chrome")
    sp.add_argument("--limit", type=int, default=50,
                    help="max traces when listing")
    sp.set_defaults(fn=cmd_trace)

    sp = sub.add_parser(
        "metrics", help="list stored metric series, or query one by name")
    sp.add_argument("name", nargs="?", default=None,
                    help="metric name; omit to list the series catalogue")
    sp.add_argument("--address", default=None)
    sp.add_argument("--prefix", default="",
                    help="name prefix filter when listing")
    sp.add_argument("--tags", default=None,
                    help="tag filter, k=v[,k2=v2]")
    sp.add_argument("--since", type=float, default=None,
                    help="epoch-seconds lower bound")
    sp.add_argument("--until", type=float, default=None,
                    help="epoch-seconds upper bound")
    sp.add_argument("--limit", type=int, default=20,
                    help="max points printed per series")
    sp.add_argument("--watch", action="store_true",
                    help="re-query every --interval seconds")
    sp.add_argument("--interval", type=float, default=5.0)
    sp.set_defaults(fn=cmd_metrics)

    sp = sub.add_parser(
        "profile",
        help="capture an on-demand XPlane (jax.profiler) trace cluster-wide")
    sp.add_argument("--address", default=None)
    sp.add_argument("--node", default=None,
                    help="node id (hex prefix ok); default: all alive nodes")
    sp.add_argument("--duration", type=float, default=3.0,
                    help="capture window in seconds")
    sp.add_argument("--logdir", default=None,
                    help="trace output dir on the worker host "
                         "(default: /tmp/ray_tpu_xprof/<ts>-<pid>); "
                         "with --memory, the pprof output path")
    sp.add_argument("--memory", action="store_true",
                    help="dump device (HBM) memory profiles instead of "
                         "a time trace")
    sp.add_argument("--list", action="store_true",
                    help="list registered capture artifacts and exit")
    sp.set_defaults(fn=cmd_profile)

    sp = sub.add_parser(
        "kvtier",
        help="list the cluster tiered-KV index (spilled prefix pages)")
    sp.add_argument("--address", default=None)
    sp.add_argument("--gc", action="store_true",
                    help="drop expired index entries before listing")
    sp.add_argument("--json", action="store_true",
                    help="print the raw index document instead of rows")
    sp.set_defaults(fn=cmd_kvtier)

    sp = sub.add_parser(
        "slo",
        help="tail-latency attribution: per-stage breakdown, SLO "
             "exemplars, per-replica skew (observability/attribution.py)")
    sp.add_argument("--address", default=None)
    sp.add_argument("--deployment", default=None,
                    help="restrict the breakdown to one deployment")
    sp.add_argument("--exemplars", action="store_true",
                    help="list stored exemplar summaries (newest first)")
    sp.add_argument("--exemplar", default=None, metavar="REQUEST_ID",
                    help="render one exemplar's stage waterfall "
                         "(X-Request-Id, prefix ok)")
    sp.add_argument("--kind", default=None,
                    choices=("violation", "baseline"),
                    help="filter --exemplars by kind")
    sp.add_argument("--limit", type=int, default=50)
    sp.add_argument("--replica-skew", action="store_true",
                    help="also print the per-replica skew table")
    sp.add_argument("--out", default=None,
                    help="with --exemplar: write a chrome-trace JSON "
                         "instead of the text waterfall")
    sp.add_argument("--json", action="store_true")
    sp.set_defaults(fn=cmd_slo)

    sp = sub.add_parser(
        "events",
        help="flight recorder: tail the cluster event journal / render "
             "a postmortem timeline (observability/events.py)")
    sp.add_argument("--address", default=None)
    sp.add_argument("--tail", type=int, default=50, metavar="N",
                    help="show the last N matching events (default 50)")
    sp.add_argument("--since", type=float, default=None, metavar="SECONDS",
                    help="only events from the last SECONDS")
    sp.add_argument("--kind", default=None,
                    help="filter by event kind (e.g. replica_death)")
    sp.add_argument("--entity", default=None,
                    help="substring match over node/deployment/replica/"
                         "request id")
    sp.add_argument("--severity", default=None,
                    choices=("INFO", "WARNING", "ERROR"),
                    help="minimum severity (WARNING hides INFO)")
    sp.add_argument("--postmortem", type=float, default=None,
                    metavar="WINDOW_S",
                    help="render one ordered incident timeline for the "
                         "trailing window: events + metric spikes + SLO "
                         "exemplars")
    sp.add_argument("--json", action="store_true")
    sp.set_defaults(fn=cmd_events)

    sp = sub.add_parser(
        "lint",
        help="run graftlint (AST concurrency/JAX-hygiene passes) against "
             "the committed findings baseline")
    sp.add_argument("paths", nargs="*",
                    help="files/dirs to analyze (default: the ray_tpu "
                         "package)")
    sp.add_argument("--json", action="store_true",
                    help="machine-readable findings document on stdout")
    sp.add_argument("--baseline", action="store_true",
                    help="regenerate GRAFTLINT_BASELINE.json from this "
                         "run (keeps surviving justifications)")
    sp.add_argument("--baseline-file", default=None,
                    help="alternate baseline path (default: repo root)")
    sp.add_argument("--tests", action="store_true",
                    help="also run tests-scoped passes (tier1-marks)")
    sp.set_defaults(fn=cmd_lint)

    args = p.parse_args(argv)
    if args.cmd == "submit" and args.entrypoint \
            and args.entrypoint[0] == "--":
        args.entrypoint = args.entrypoint[1:]
    args.fn(args)


if __name__ == "__main__":
    main()
