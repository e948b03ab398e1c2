"""Dashboard HTTP server (see package docstring)."""

from __future__ import annotations

import asyncio
import json
import threading
from typing import Optional

import ray_tpu

_INDEX = """<!doctype html>
<html><head><title>ray_tpu dashboard</title>
<style>
 body { font-family: monospace; margin: 2em; }
 table { border-collapse: collapse; margin-bottom: 2em; }
 td, th { border: 1px solid #999; padding: 4px 8px; text-align: left; }
 th { background: #eee; }
 h2 { margin-bottom: 4px; }
</style></head>
<body>
<h1>ray_tpu dashboard</h1>
<div>
 <button onclick="profile()">profile cluster (3s)</button>
 <span id="profstatus"></span>
 · <a href="/profiling">engine profiling &amp; XProf captures</a>
</div>
<pre id="profout" style="max-height:300px;overflow:auto;background:#f7f7f7"></pre>
<div id="charts"></div>
<h2>metrics (control-plane time-series store)</h2>
<div id="metriccharts">no stored series yet</div>
<div id="content">loading…</div>
<script>
function esc(s) {
  // user-controlled strings (actor names, entrypoints) must never reach
  // innerHTML unescaped
  return String(s).replace(/&/g, "&amp;").replace(/</g, "&lt;")
          .replace(/>/g, "&gt;").replace(/"/g, "&quot;");
}
function sparkline(samples, key, label) {
  const vals = samples.map(s => s[key]).filter(v => v !== null && v !== undefined);
  if (!vals.length) return "";
  const w = 360, h = 60, max = Math.max(...vals, 1e-9);
  const pts = vals.map((v, i) =>
    (i * w / Math.max(1, vals.length - 1)).toFixed(1) + "," +
    (h - v * h / max).toFixed(1)).join(" ");
  return "<div><b>" + esc(label) + "</b> (now " + esc(vals[vals.length-1]) +
    ", max " + esc(max.toFixed(1)) + ")<br>" +
    "<svg width='" + w + "' height='" + h + "' style='border:1px solid #ccc'>" +
    "<polyline fill='none' stroke='#36c' stroke-width='1.5' points='" +
    pts + "'/></svg></div>";
}
async function profile() {
  document.getElementById("profstatus").textContent = "sampling…";
  const out = await (await fetch("/api/profile?duration=3")).json();
  document.getElementById("profout").textContent =
    out.collapsed.slice(0, 80).join("\\n");
  document.getElementById("profstatus").textContent =
    out.rounds + " rounds";
}
async function refreshMetrics() {
  // CP time-series panel: busiest stored series, one sparkline per metric
  const cat = await (await fetch("/api/metrics/series")).json();
  const byName = {};
  for (const row of cat) {
    if (!byName[row.name] || row.points > byName[row.name].points)
      byName[row.name] = row;
  }
  const top = Object.values(byName)
    .sort((a, b) => b.points - a.points).slice(0, 6);
  let html = "";
  for (const row of top) {
    const q = await (await fetch("/api/metrics/query?name=" +
      encodeURIComponent(row.name))).json();
    if (!q.series || !q.series.length) continue;
    // histogram points are {buckets,sum,count} dicts: chart the count
    const samples = q.series[0].points.map(p => ({v:
      (p[1] !== null && typeof p[1] === "object") ? p[1].count : p[1]}));
    html += sparkline(samples, "v",
      row.name + (Object.keys(row.tags || {}).length
                  ? " " + JSON.stringify(row.tags) : ""));
  }
  if (html) document.getElementById("metriccharts").innerHTML = html;
}
async function refresh() {
  await refreshMetrics().catch(() => {});
  const ts = await (await fetch("/api/timeseries")).json();
  document.getElementById("charts").innerHTML =
    sparkline(ts, "cpu_percent_avg", "cluster cpu %") +
    sparkline(ts, "memory_percent_avg", "cluster mem %") +
    sparkline(ts, "logical_cpus_in_use", "logical CPUs in use") +
    sparkline(ts, "object_store_used_bytes", "object store bytes");
  const sections = ["nodes", "train", "serve", "autoscaler", "actors", "pgs", "jobs", "tasks", "traces", "kvtier", "slo", "events"];
  let html = "";
  for (const s of sections) {
    const rows = await (await fetch("/api/" + s)).json();
    html += "<h2>" + esc(s) + " (" + rows.length + ")</h2>";
    if (rows.length) {
      const cols = Object.keys(rows[0]);
      html += "<table><tr>" + cols.map(c => "<th>" + esc(c) + "</th>").join("") + "</tr>";
      for (const r of rows.slice(0, 200)) {
        html += "<tr>" + cols.map(c => {
          let cell = esc(JSON.stringify(r[c]));
          if (s === "nodes" && c === "node_id" && typeof r[c] === "string") {
            cell = "<a href='/api/node/" + encodeURIComponent(r[c]) + "'>" +
                   cell + "</a>";
          }
          if (s === "traces" && c === "trace_id" && typeof r[c] === "string") {
            cell = "<a href='/trace/" + encodeURIComponent(r[c]) + "'>" +
                   cell + "</a>";
          }
          if (s === "slo" && c === "request_id" && typeof r[c] === "string") {
            cell = "<a href='/slo/" + encodeURIComponent(r[c]) + "'>" +
                   cell + "</a>";
          }
          if (s === "events" &&
              ["node", "deployment", "replica", "request_id"].includes(c) &&
              typeof r[c] === "string") {
            // per-entity drill-down: every event touching this entity
            cell = "<a href='/events?entity=" + encodeURIComponent(r[c]) +
                   "'>" + cell + "</a>";
          }
          return "<td>" + cell + "</td>";
        }).join("") + "</tr>";
      }
      html += "</table>";
    }
  }
  document.getElementById("content").innerHTML = html;
}
refresh(); setInterval(refresh, 3000);
</script></body></html>"""


def _train_runs() -> list[dict]:
    """Train runs published by TrainController to the CP KV
    (train_run:* keys; reference: dashboard/modules/train/)."""
    from ray_tpu.core import api
    rt = api._get_runtime()
    keys = rt.cp_client.call_with_retry(
        "kv_keys", {"prefix": "train_run:"}, timeout=10.0) or []
    out = []
    for key in sorted(keys):
        raw = rt.cp_client.call_with_retry("kv_get", {"key": key},
                                           timeout=10.0)
        if raw is None:
            continue
        try:
            out.append(json.loads(raw.decode()
                                  if isinstance(raw, bytes) else raw))
        except ValueError:
            continue
    return out


def _autoscaler_state() -> list[dict]:
    """Instance lifecycle rows published by autoscalers to the CP KV
    (one key per scaler — stacked autoscalers merge here; reference:
    dashboard cluster view's autoscaler status)."""
    from ray_tpu.core import api
    rt = api._get_runtime()
    keys = rt.cp_client.call_with_retry(
        "kv_keys", {"prefix": "autoscaler:instances"}, timeout=10.0) or []
    rows: list[dict] = []
    for key in sorted(keys):
        raw = rt.cp_client.call_with_retry("kv_get", {"key": key},
                                           timeout=10.0)
        if raw is None:
            continue
        try:
            state = json.loads(raw.decode()
                               if isinstance(raw, bytes) else raw)
        except ValueError:
            continue
        scaler = key.rsplit(":", 1)[-1]
        # a stopped/crashed scaler's key may linger (stop() best-effort
        # deletes it, but the CP can outlive that notify): hide rows whose
        # publisher has gone quiet instead of showing dead instances
        import time as _time
        if _time.time() - float(state.get("updated_at") or 0) > 60.0:
            continue
        rows.extend({"scaler": scaler, **i}
                    for i in state.get("instances") or [])
    return rows


def _serve_apps() -> list[dict]:
    """Serve deployment/replica status with live queue lengths via the
    controller (reference: dashboard/modules/serve/). Empty when serve is
    down."""
    try:
        controller = ray_tpu.get_actor("_serve_controller", timeout=1.0)
    except Exception:  # noqa: BLE001 — serve not running
        return []
    try:
        status = ray_tpu.get(controller.detailed_status.remote(),
                             timeout=15.0)
    except Exception:  # noqa: BLE001
        return []
    rows = [{"deployment": name, **info} for name, info in status.items()]
    # elastic fleet (ISSUE 17): compact the scale-decision flight recorder
    # into "from->to reason" strings so the table cell stays readable —
    # the raw records (with signals) remain on detailed_status
    for row in rows:
        decs = row.get("scale_decisions")
        if decs:
            row["scale_decisions"] = [
                f"{d.get('from')}->{d.get('to')} {d.get('reason')}"
                for d in decs[-5:]]
    # cache-aware routing counters (ISSUE 10) ride along per deployment:
    # summed across every router that reported to the metrics store
    try:
        from ray_tpu.util import state as _state
        for row in rows:
            dep = row["deployment"].split("#")[-1]
            aff = {}
            for short, metric in (
                    ("hits", "ray_tpu_serve_router_affinity_hits_total"),
                    ("spillovers",
                     "ray_tpu_serve_router_affinity_spillovers_total"),
                    ("stale_fallbacks",
                     "ray_tpu_serve_router_affinity_stale_fallbacks_total")):
                res = _state.query_metrics(metric, tags={"deployment": dep})
                series = (res or {}).get("series") or []
                if series:
                    aff[short] = sum(s["points"][-1][1] for s in series
                                     if s.get("points"))
            if aff.get("hits") or aff.get("spillovers") or \
                    aff.get("stale_fallbacks"):
                row["affinity"] = aff
    except Exception:  # noqa: BLE001 — counters are best-effort decoration
        pass
    return rows


def _collapse_stacks(proc: str, text: str) -> list[str]:
    """Parse dump_thread_stacks text into collapsed flamegraph lines:
    'proc;thread;frame;frame;...' (root first)."""
    out = []
    for block in text.split("--- thread "):
        block = block.strip()
        if not block:
            continue
        lines = block.splitlines()
        header = lines[0].rsplit(" (", 1)[0].strip()
        frames = []
        for line in lines[1:]:
            line = line.strip()
            if line.startswith("File \""):
                try:
                    path, _, rest = line[6:].partition("\", line ")
                    _lineno, _, func = rest.partition(", in ")
                    frames.append(f"{path.rsplit('/', 1)[-1]}:{func.strip()}")
                except ValueError:
                    continue
        if frames:
            out.append(";".join([proc, header] + frames))
    return out


def _hexify(obj):
    """IDs → hex strings for JSON."""
    if isinstance(obj, dict):
        return {k: _hexify(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_hexify(v) for v in obj]
    if isinstance(obj, (int, float, bool)) or obj is None:
        return obj
    if hasattr(obj, "hex") and not isinstance(obj, (str, bytes)):
        try:
            return obj.hex()[:16]
        except Exception:  # noqa: BLE001
            return str(obj)
    if isinstance(obj, bytes):
        return obj.hex()[:16]
    return obj


_KIND_COLORS = {"submit": "#36c", "server": "#383", "scheduler": "#a60",
                "object": "#888", "llm": "#a3a", "internal": "#555"}


def _render_waterfall(trace: dict) -> str:
    """Server-rendered waterfall HTML for one trace: spans sorted into
    parent-first DFS order, each a bar offset/sized by its wall-clock
    window relative to the trace extent."""
    import html as _html

    spans = trace.get("spans") or []
    if not spans:
        return "<html><body>empty trace</body></html>"
    t0 = min(s.get("start") or 0.0 for s in spans)
    t1 = max((s.get("end") or s.get("start") or 0.0) for s in spans)
    total = max(t1 - t0, 1e-6)
    by_id = {s.get("span_id"): s for s in spans}
    children: dict = {}
    roots = []
    for s in sorted(spans, key=lambda s: s.get("start") or 0.0):
        pid = s.get("parent_id")
        if pid and pid in by_id:
            children.setdefault(pid, []).append(s)
        else:
            roots.append(s)
    ordered: list[tuple[dict, int]] = []

    def walk(s, depth):
        ordered.append((s, depth))
        for c in children.get(s.get("span_id"), []):
            walk(c, depth + 1)

    for r in roots:
        walk(r, 0)
    rows = []
    for s, depth in ordered:
        start = (s.get("start") or t0) - t0
        dur = max(((s.get("end") or s.get("start") or t0) - t0) - start, 0.0)
        left = 100.0 * start / total
        width = max(100.0 * dur / total, 0.15)
        color = ("#c33" if s.get("status") == "error"
                 else _KIND_COLORS.get(s.get("kind"), "#555"))
        name = _html.escape(str(s.get("name", "span")))
        label = (f"{name} — {dur * 1e3:.2f} ms "
                 f"[{_html.escape(str(s.get('kind', '')))}]")
        rows.append(
            f"<div class='row'>"
            f"<div class='label' style='padding-left:{depth * 14}px'"
            f" title='{_html.escape(json.dumps(s.get('attrs') or {}))}'>"
            f"{name}</div>"
            f"<div class='lane'><div class='bar' title='{label}'"
            f" style='left:{left:.2f}%;width:{width:.2f}%;"
            f"background:{color}'></div></div>"
            f"<div class='dur'>{dur * 1e3:.2f} ms</div></div>")
    meta = trace.get("meta") or {}
    head = _html.escape(str(meta.get("name", "")))
    tid = _html.escape(str(trace.get("trace_id", "")))
    return f"""<!doctype html>
<html><head><title>trace {tid[:16]}</title><style>
 body {{ font-family: monospace; margin: 2em; }}
 .row {{ display: flex; align-items: center; height: 18px; }}
 .label {{ width: 340px; overflow: hidden; white-space: nowrap;
           text-overflow: ellipsis; flex-shrink: 0; }}
 .lane {{ position: relative; flex-grow: 1; height: 12px;
          background: #f4f4f4; border-left: 1px solid #ccc; }}
 .bar {{ position: absolute; height: 12px; border-radius: 2px; }}
 .dur {{ width: 110px; text-align: right; flex-shrink: 0; color: #666; }}
</style></head><body>
<h1>trace {tid[:16]}… — {head}</h1>
<p>{len(spans)} spans over {total * 1e3:.2f} ms ·
 <a href="/api/trace/{tid}">raw JSON</a> · <a href="/">dashboard</a></p>
{''.join(rows)}
</body></html>"""


class _Timeseries:
    """In-process ring buffer of cluster gauges, sampled by a background
    thread (reference: dashboard/modules/metrics keeps timeseries in
    Prometheus; here the dashboard itself retains a window so the UI has
    history without external infra)."""

    def __init__(self, period_s: float = 5.0, window: int = 720):
        self.period_s = period_s
        self.window = window
        self.samples: list[dict] = []
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def start(self):
        if self._thread is None:
            self._thread = threading.Thread(target=self._loop, daemon=True,
                                            name="dash-timeseries")
            self._thread.start()

    def _loop(self):
        import time as _time
        while not self._stop.wait(self.period_s):
            try:
                from ray_tpu.core import api
                rt = api._try_get_runtime()
                if rt is None:
                    continue
                nodes = rt.cp_client.call_with_retry(
                    "get_node_metrics", None, timeout=10.0)
                alive = [n for n in nodes if n.get("alive")]
                cpu = [n["metrics"].get("cpu_percent") for n in alive
                       if n["metrics"].get("cpu_percent") is not None]
                mem = [n["metrics"].get("memory_percent") for n in alive
                       if n["metrics"].get("memory_percent") is not None]
                store = sum(n["metrics"].get("object_store_used_bytes", 0)
                            for n in alive)
                used_cpu = sum(
                    n["resources"].get("CPU", 0)
                    - n["available"].get("CPU", 0) for n in alive)
                sample = {
                    "ts": _time.time(),
                    "nodes_alive": len(alive),
                    "nodes_draining": sum(
                        1 for n in alive
                        if n.get("state") == "DRAINING"),
                    "cpu_percent_avg": round(sum(cpu) / len(cpu), 2)
                    if cpu else None,
                    "memory_percent_avg": round(sum(mem) / len(mem), 2)
                    if mem else None,
                    "object_store_used_bytes": store,
                    "logical_cpus_in_use": round(used_cpu, 2),
                }
                with self._lock:
                    self.samples.append(sample)
                    if len(self.samples) > self.window:
                        del self.samples[: len(self.samples) - self.window]
            except Exception:  # noqa: BLE001 — sampling is best-effort
                pass

    def snapshot(self) -> list[dict]:
        with self._lock:
            return list(self.samples)

    def stop(self):
        self._stop.set()


class Dashboard:
    def __init__(self, host: str = "127.0.0.1", port: int = 8265,
                 timeseries_period_s: float = 5.0):
        self.host = host
        self.port = port
        self._thread: Optional[threading.Thread] = None
        self._loop = None
        self._started = threading.Event()
        self._timeseries = _Timeseries(period_s=timeseries_period_s)

    def start(self):
        self._timeseries.start()
        self._thread = threading.Thread(target=self._serve, daemon=True,
                                        name="dashboard")
        self._thread.start()
        if not self._started.wait(10.0):
            raise RuntimeError("dashboard failed to start")
        return self

    def stop(self):
        self._timeseries.stop()
        if self._loop is not None:
            self._loop.call_soon_threadsafe(self._loop.stop)

    def _serve(self):
        from aiohttp import web

        loop = asyncio.new_event_loop()
        asyncio.set_event_loop(loop)
        self._loop = loop
        app = web.Application()
        app.router.add_get("/", self._index)
        app.router.add_get("/metrics", self._metrics)
        app.router.add_get("/api/node/{node_id}", self._node_detail)
        app.router.add_get("/api/profile", self._profile)
        app.router.add_get("/api/profile/artifacts",
                           self._profile_artifacts)
        app.router.add_get("/api/profile/download/{artifact_id}",
                           self._profile_download)
        app.router.add_get("/profiling", self._profiling_view)
        app.router.add_get("/api/trace/{trace_id}", self._trace_detail)
        app.router.add_get("/trace/{trace_id}", self._trace_view)
        app.router.add_get("/api/slo/report", self._slo_report)
        app.router.add_get("/slo/{request_id}", self._slo_exemplar_view)
        app.router.add_get("/events", self._events_view)
        app.router.add_get("/api/metrics/query", self._metrics_query)
        app.router.add_get("/api/metrics/series", self._metrics_series)
        app.router.add_get("/api/{section}", self._api)
        runner = web.AppRunner(app)
        loop.run_until_complete(runner.setup())
        site = web.TCPSite(runner, self.host, self.port)
        loop.run_until_complete(site.start())
        if self.port == 0:
            for s in site._server.sockets:
                self.port = s.getsockname()[1]
                break
        self._started.set()
        try:
            loop.run_forever()
        finally:
            loop.run_until_complete(runner.cleanup())
            loop.close()

    async def _index(self, request):
        from aiohttp import web
        return web.Response(text=_INDEX, content_type="text/html")

    async def _metrics(self, request):
        """Prometheus scrape endpoint (reference: dashboard/modules/metrics/
        + per-node reporter agents; here the CP aggregates node gauges)."""
        from aiohttp import web
        loop = asyncio.get_event_loop()

        def fetch():
            from ray_tpu.core import api
            from ray_tpu.util import metrics as _m
            rt = api._get_runtime()
            # one render over CP dump + this process's registry: same-name
            # series merge (counters sum, histogram buckets add), HELP/TYPE
            # emitted once, no duplicate series. The local flusher's source
            # is excluded from the dump — the registry here is fresher than
            # its last flush, and counting both would double it.
            local = _m._collect_dicts()
            exclude = [s for s in (_m.flusher_source(),) if s]
            dump = rt.cp_client.call_with_retry(
                "metrics_dump", {"exclude_sources": exclude}, timeout=10.0)
            if dump is None:
                dump = {"metrics": []}
            return _m.render_exposition(dump["metrics"] + local)

        text = await loop.run_in_executor(None, fetch)
        return web.Response(text=text, content_type="text/plain")

    async def _metrics_query(self, request):
        """JSON time-series query against the CP store:
        /api/metrics/query?name=...&since=...&until=...&tag.KEY=VALUE"""
        from aiohttp import web
        loop = asyncio.get_event_loop()
        name = request.query.get("name", "")
        tags = {k[4:]: v for k, v in request.query.items()
                if k.startswith("tag.")}

        def _f(key):
            raw = request.query.get(key)
            try:
                return float(raw) if raw is not None else None
            except ValueError:
                return None

        since, until = _f("since"), _f("until")

        def fetch():
            from ray_tpu.util import state
            return state.query_metrics(name, tags=tags or None,
                                       since=since, until=until)

        result = await loop.run_in_executor(None, fetch)
        if result is None:
            return web.json_response(
                {"error": f"unknown metric: {name}"}, status=404)
        return web.json_response(result)

    async def _metrics_series(self, request):
        """Catalogue of stored series: /api/metrics/series?prefix=..."""
        from aiohttp import web
        loop = asyncio.get_event_loop()
        prefix = request.query.get("prefix", "")

        def fetch():
            from ray_tpu.util import state
            return state.list_metric_series(prefix=prefix)

        return web.json_response(await loop.run_in_executor(None, fetch))

    async def _api(self, request):
        from aiohttp import web

        section = request.match_info["section"]
        loop = asyncio.get_event_loop()

        def fetch():
            from ray_tpu.util import state
            if section == "nodes":
                return ray_tpu.nodes()
            if section == "actors":
                return state.list_actors()
            if section == "tasks":
                return state.list_tasks(limit=200)
            if section == "pgs":
                return state.list_placement_groups()
            if section == "jobs":
                from ray_tpu.job import JobSubmissionClient
                return JobSubmissionClient().list_jobs()
            if section == "train":
                return _train_runs()
            if section == "autoscaler":
                return _autoscaler_state()
            if section == "serve":
                return _serve_apps()
            if section == "traces":
                return state.list_traces(limit=100)
            if section == "slo":
                # SLO exemplar summaries (same CP query `ray-tpu slo
                # --exemplars` renders); request_id cells link to the
                # per-request stage waterfall at /slo/<request_id>
                return state.list_slo_exemplars(limit=100)
            if section == "events":
                # flight-recorder journal rows (same CP query `ray-tpu
                # events` renders); entity cells link to the /events
                # drill-down panel
                return state.list_events(
                    kind=request.query.get("kind"),
                    severity=request.query.get("severity"),
                    entity=request.query.get("entity"),
                    limit=int(request.query.get("limit", "200")))
            if section == "kvtier":
                # tiered-KV prefix index rows (same CP query `ray-tpu
                # kvtier` renders); the generic section loop tables them.
                # Leading summary rows give stored-vs-raw bytes per tier
                # and the effective codec ratio (= capacity multiplier
                # on the tier byte caps)
                ents = (state.list_kv_tier() or {}).get("entries") or []
                agg: dict = {}
                for e in ents:
                    a = agg.setdefault(e.get("tier", "?"),
                                       {"entries": 0, "enc": 0, "raw": 0})
                    a["entries"] += 1
                    a["enc"] += int(e.get("nbytes") or 0)
                    a["raw"] += int(e.get("raw") or e.get("nbytes") or 0)
                summary = [
                    {"tier": t, "entries": a["entries"],
                     "bytes_stored": a["enc"], "bytes_raw": a["raw"],
                     "codec_ratio": round(a["raw"] / a["enc"], 3)
                     if a["enc"] else 0.0}
                    for t, a in sorted(agg.items())]
                return summary + ents
            if section == "timeseries":
                return self._timeseries.snapshot()
            if section == "logs":
                wid = request.query.get("worker_id")
                tail = int(request.query.get("tail", "100"))
                logs = state.worker_logs(worker_id=wid, tail=tail)
                return [{"file": k, "content": v} for k, v in logs.items()]
            if section == "stacks":
                # on-demand whole-cluster stack snapshot (ref: dashboard
                # reporter profiling endpoints) — hang diagnosis in one GET
                return [{"process": k, "stacks": v}
                        for k, v in state.dump_cluster_stacks().items()]
            return None

        data = await loop.run_in_executor(None, fetch)
        if data is None:
            return web.Response(status=404, text=f"unknown section {section}")
        return web.json_response(_hexify(data))

    async def _node_detail(self, request):
        """Per-node drill-down: identity, resources, live gauges, and the
        node's actors (reference: dashboard node detail page)."""
        from aiohttp import web

        node_id = request.match_info["node_id"]
        loop = asyncio.get_event_loop()

        def fetch():
            from ray_tpu.core import api
            from ray_tpu.util import state
            rt = api._get_runtime()
            nodes = rt.cp_client.call_with_retry(
                "get_node_metrics", None, timeout=10.0)
            me = next((n for n in nodes
                       if n["node_id"].hex().startswith(node_id)), None)
            if me is None:
                return None
            actors = [a for a in state.list_actors()
                      if str(a.get("node_id", ""))
                      .startswith(node_id[:8])]
            return {**me, "actors": actors}

        data = await loop.run_in_executor(None, fetch)
        if data is None:
            return web.Response(status=404, text=f"unknown node {node_id}")
        return web.json_response(_hexify(data))

    async def _trace_detail(self, request):
        """Raw spans of one trace as JSON (id prefix ok)."""
        from aiohttp import web

        trace_id = request.match_info["trace_id"]
        loop = asyncio.get_event_loop()

        def fetch():
            from ray_tpu.util import state
            return state.get_trace(trace_id)

        data = await loop.run_in_executor(None, fetch)
        if data is None:
            return web.Response(status=404,
                                text=f"unknown trace {trace_id}")
        return web.json_response(_hexify(data))

    async def _trace_view(self, request):
        """Per-trace waterfall: one bar per span, positioned by start
        offset and duration, indented by parent depth (reference: the
        dashboard's task timeline view, collapsed to one trace)."""
        from aiohttp import web

        trace_id = request.match_info["trace_id"]
        loop = asyncio.get_event_loop()

        def fetch():
            from ray_tpu.util import state
            return state.get_trace(trace_id)

        data = await loop.run_in_executor(None, fetch)
        if data is None:
            return web.Response(status=404,
                                text=f"unknown trace {trace_id}")
        return web.Response(text=_render_waterfall(data),
                            content_type="text/html")

    async def _slo_report(self, request):
        """Fleet tail-latency breakdown: per-stage percentiles, dominant
        stage, per-replica skew (same aggregation `ray-tpu slo` prints).
        Optional ?deployment=<name> filter."""
        from aiohttp import web

        deployment = request.query.get("deployment")
        loop = asyncio.get_event_loop()

        def fetch():
            from ray_tpu.util import state
            return state.slo_report(deployment=deployment)

        return web.json_response(
            _hexify(await loop.run_in_executor(None, fetch)))

    async def _slo_exemplar_view(self, request):
        """Per-request critical-path waterfall: the stored SLO exemplar's
        stage timeline rendered through the same waterfall renderer the
        trace view uses (stages become child spans of one root)."""
        from aiohttp import web

        rid = request.match_info["request_id"]
        loop = asyncio.get_event_loop()

        def fetch():
            from ray_tpu.util import state
            return state.get_slo_exemplar(rid)

        rec = await loop.run_in_executor(None, fetch)
        if rec is None:
            return web.Response(status=404,
                                text=f"unknown exemplar {rid}")
        from ray_tpu.observability import attribution
        kind = rec.get("kind", "?")
        label = (f"request {rec.get('request_id', rid)} [{kind}"
                 f"{', violated: ' + ','.join(rec['violated']) if rec.get('violated') else ''}]")
        trace = {"spans": attribution.stages_to_spans(rec),
                 "meta": {"name": label},
                 "trace_id": rec.get("trace_id") or rec.get("request_id", rid)}
        return web.Response(text=_render_waterfall(trace),
                            content_type="text/html")

    async def _events_view(self, request):
        """Flight-recorder panel: the journal filtered by
        ?entity=/&kind=/&severity=, newest first, with per-entity
        drill-down links (ISSUE 19)."""
        from aiohttp import web

        kind = request.query.get("kind")
        severity = request.query.get("severity")
        entity = request.query.get("entity")
        loop = asyncio.get_event_loop()

        def fetch():
            from ray_tpu.util import state
            try:
                return state.list_events(kind=kind, severity=severity,
                                         entity=entity, limit=500)
            except Exception:  # noqa: BLE001 — CP down
                return []

        rows = await loop.run_in_executor(None, fetch)
        return web.Response(
            text=_render_events(rows, kind=kind, severity=severity,
                                entity=entity),
            content_type="text/html")

    async def _profile(self, request):
        """On-demand profiling. Default: repeatedly snapshot cluster (or
        one worker's) stacks for ``duration`` seconds and return collapsed
        flamegraph lines ('frame;frame;frame count') — sampling this
        dashboard's view of every process (reference: dashboard/modules/
        reporter/profile_manager.py py-spy endpoints).

        With ``?node=<id prefix>`` (or ``node=all``): capture an XPlane
        (jax.profiler) trace ON THE TARGET WORKERS instead, via the
        cluster profiling RPC (CP → node agent → worker); the response
        lists the registered artifacts, downloadable from
        /api/profile/download/<id>."""
        from aiohttp import web

        try:
            duration = min(30.0, max(0.2,
                                     float(request.query.get("duration",
                                                             "3"))))
        except ValueError:
            return web.Response(status=400, text="bad duration")
        node = request.query.get("node")
        if node is not None:
            def capture():
                from ray_tpu.util import state
                return state.capture_xprof(
                    node_id=None if node in ("", "all") else node,
                    duration=duration)

            loop = asyncio.get_event_loop()
            try:
                data = await loop.run_in_executor(None, capture)
            except Exception as e:  # noqa: BLE001 — bad node id, CP down
                return web.json_response({"error": repr(e)}, status=400)
            return web.json_response(_hexify(data))
        process = request.query.get("process")  # substring filter
        loop = asyncio.get_event_loop()

        def sample():
            import time as _time

            from ray_tpu.util import state
            counts: dict[str, int] = {}
            deadline = _time.monotonic() + duration
            rounds = 0
            while _time.monotonic() < deadline:
                try:
                    dump = state.dump_cluster_stacks()
                except Exception:  # noqa: BLE001
                    break
                rounds += 1
                for proc, text in dump.items():
                    if process and process not in proc:
                        continue
                    for stack in _collapse_stacks(proc, text):
                        counts[stack] = counts.get(stack, 0) + 1
                _time.sleep(0.2)
            lines = [f"{stack} {n}" for stack, n in
                     sorted(counts.items(), key=lambda kv: -kv[1])]
            return {"duration_s": duration, "rounds": rounds,
                    "collapsed": lines[:500]}

        data = await loop.run_in_executor(None, sample)
        return web.json_response(data)

    async def _profile_artifacts(self, request):
        """Registered XPlane/memory capture artifacts (newest first)."""
        from aiohttp import web
        loop = asyncio.get_event_loop()

        def fetch():
            from ray_tpu.util import state
            return state.list_profile_artifacts()

        return web.json_response(
            _hexify(await loop.run_in_executor(None, fetch)))

    async def _profile_download(self, request):
        """One artifact's trace directory as a .tar.gz (the logdir must be
        visible from the dashboard host — single-host clusters and shared
        filesystems; elsewhere the response 404s with the remote path so
        the operator knows where the bytes live)."""
        import io
        import os
        import tarfile

        from aiohttp import web

        art_id = request.match_info["artifact_id"]
        loop = asyncio.get_event_loop()

        def build():
            from ray_tpu.util import state
            arts = state.list_profile_artifacts()
            art = next((a for a in arts
                        if str(a.get("id", "")).startswith(art_id)), None)
            if art is None:
                return None, f"unknown artifact {art_id}"
            logdir = art.get("logdir") or ""
            if not os.path.isdir(logdir):
                return None, (f"artifact {art['id']} logdir not on this "
                              f"host: {logdir}")
            buf = io.BytesIO()
            with tarfile.open(fileobj=buf, mode="w:gz") as tar:
                tar.add(logdir, arcname=os.path.basename(
                    logdir.rstrip("/")) or "profile")
            return buf.getvalue(), art["id"]

        data, info = await loop.run_in_executor(None, build)
        if data is None:
            return web.Response(status=404, text=info)
        return web.Response(
            body=data, content_type="application/gzip",
            headers={"Content-Disposition":
                     f'attachment; filename="xprof-{info}.tar.gz"'})

    async def _profiling_view(self, request):
        """Server-rendered profiling panel: per-replica engine phase
        p50/p95 + compile/memory introspection (serve detailed_status)
        and the registered capture artifacts with download links."""
        from aiohttp import web

        loop = asyncio.get_event_loop()

        def fetch():
            from ray_tpu.util import state
            apps = _serve_apps()
            try:
                arts = state.list_profile_artifacts()
            except Exception:  # noqa: BLE001 — CP down
                arts = []
            return apps, arts

        apps, arts = await loop.run_in_executor(None, fetch)
        return web.Response(text=_render_profiling(apps, arts),
                            content_type="text/html")


def _render_events(rows: list[dict], kind=None, severity=None,
                   entity=None) -> str:
    """HTML for the /events panel (same server-rendered idiom as the
    profiling panel). Entity cells self-link so any event pivots to
    that entity's full history."""
    import html as _html
    import time as _time

    filt = " ".join(f"{k}={v}" for k, v in
                    (("kind", kind), ("severity", severity),
                     ("entity", entity)) if v)
    head = (f"<h1>flight recorder</h1><p>{len(rows)} event(s)"
            f"{' — filter: ' + _html.escape(filt) if filt else ''}"
            f" · <a href='/events'>clear filters</a>"
            f" · <a href='/'>dashboard</a></p>")
    cols = ("ts", "severity", "kind", "node", "deployment", "replica",
            "request_id", "reason", "attrs")
    parts = [head, "<table border=1 cellspacing=0 cellpadding=3><tr>"]
    parts.extend(f"<th>{c}</th>" for c in cols)
    parts.append("</tr>")
    for ev in rows:
        parts.append("<tr>")
        for c in cols:
            v = ev.get(c)
            if c == "ts" and v:
                v = _time.strftime("%H:%M:%S",
                                   _time.localtime(float(v))) \
                    + f".{int(float(v) * 1000) % 1000:03d}"
            cell = _html.escape("" if v is None else
                                (json.dumps(v) if isinstance(v, dict)
                                 else str(v)))
            if c in ("node", "deployment", "replica", "request_id") \
                    and ev.get(c):
                from urllib.parse import quote
                cell = (f"<a href='/events?entity={quote(str(ev[c]))}'>"
                        f"{cell}</a>")
            parts.append(f"<td>{cell}</td>")
        parts.append("</tr>")
    parts.append("</table>")
    return ("<html><head><title>flight recorder</title></head><body>"
            + "".join(parts) + "</body></html>")


def _render_profiling(apps: list[dict], artifacts: list[dict]) -> str:
    """HTML for the /profiling panel (same server-rendered idiom as the
    trace waterfall)."""
    import html as _html
    import time as _time

    from ray_tpu.observability.profiling import STARTUP_TOTALS

    phase_keys = ["queue_wait", "admit", "prefill", "chunk_prefill",
                  "decode_dispatch", "verify_dispatch", "harvest", "fetch"]
    scalar_keys = ["itl_s", "compile_events", "mid_traffic_compiles",
                   "compile_s", *STARTUP_TOTALS,
                   "host_stall_n", "host_stall_s_total",
                   "gc_pause_n", "gc_pause_s_total", "gc_pause_max_ms",
                   "dry_dispatches_total", "dry_s_total",
                   "idle_lead_k", "lead_climbs_total",
                   "kv_page_occupancy", "weights_bytes",
                   "kv_pool_bytes", "device_bytes_in_use"]
    sections = []
    for app in apps:
        engines = app.get("engine") or []
        name = _html.escape(str(app.get("deployment", "?")))
        rows = []
        for i, eng in enumerate(engines):
            if not isinstance(eng, dict):
                continue
            cells = [f"<td>replica {i}</td>"]
            for p in phase_keys:
                p50 = eng.get(f"phase_{p}_p50_ms")
                p95 = eng.get(f"phase_{p}_p95_ms")
                cells.append(
                    "<td>—</td>" if p50 is None else
                    f"<td>{p50:.2f} / {p95:.2f}</td>")
            for k in scalar_keys:
                v = eng.get(k)
                cells.append(f"<td>{_html.escape(str(v))}</td>")
            rows.append("<tr>" + "".join(cells) + "</tr>")
        if not rows:
            continue
        head = ("<tr><th></th>"
                + "".join(f"<th>{p}<br>p50/p95 ms</th>"
                          for p in phase_keys)
                + "".join(f"<th>{k}</th>" for k in scalar_keys) + "</tr>")
        sections.append(f"<h2>{name}</h2><table>{head}{''.join(rows)}"
                        "</table>")
    art_rows = []
    for a in artifacts:
        aid = _html.escape(str(a.get("id", "")))
        age = _time.time() - float(a.get("ts") or 0)
        art_rows.append(
            "<tr>"
            f"<td><a href='/api/profile/download/{aid}'>{aid}</a></td>"
            f"<td>{_html.escape(str(a.get('kind', '')))}</td>"
            f"<td>{_html.escape(str(a.get('node_id', ''))[:12])}</td>"
            f"<td>{_html.escape(str(a.get('worker_id', ''))[:12])}</td>"
            f"<td>{_html.escape(str(a.get('duration_s', '')))}</td>"
            f"<td>{_html.escape(str(a.get('logdir', '')))}</td>"
            f"<td>{age:.0f}s ago</td></tr>")
    arts_html = (
        "<table><tr><th>artifact</th><th>kind</th><th>node</th>"
        "<th>worker</th><th>dur s</th><th>logdir</th><th>age</th></tr>"
        + "".join(art_rows) + "</table>" if art_rows
        else "<p>no captures yet</p>")
    body = ("".join(sections)
            or "<p>no LLM engine replicas reporting (deploy a serve LLM "
               "app, then reload)</p>")
    return f"""<!doctype html>
<html><head><title>ray_tpu profiling</title><style>
 body {{ font-family: monospace; margin: 2em; }}
 table {{ border-collapse: collapse; margin-bottom: 2em; }}
 td, th {{ border: 1px solid #999; padding: 4px 8px; text-align: left; }}
 th {{ background: #eee; }}
</style></head><body>
<h1>engine profiling</h1>
<p><a href="/">dashboard</a> ·
 capture an XPlane trace: <code>GET /api/profile?node=all&amp;duration=3</code>
 or <code>ray-tpu profile --node &lt;id&gt; --duration 3</code></p>
{body}
<h2>capture artifacts</h2>
{arts_html}
</body></html>"""


def start_dashboard(host: str = "127.0.0.1", port: int = 8265) -> Dashboard:
    return Dashboard(host, port).start()
