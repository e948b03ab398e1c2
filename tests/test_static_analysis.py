"""graftlint acceptance: per-pass positive/negative fixtures, pragma
suppression, baseline exact-drift (both directions), CLI JSON, and
regression fixtures for the production findings this PR fixed.

Everything here is pure-AST analysis of inline source strings or of the
repo itself — no cluster, no JAX import, sub-second per test. The one
full-package run doubles as the tier-1 gate: it must match the committed
GRAFTLINT_BASELINE.json exactly and finish well inside 15 seconds.
"""

import io
import json
import textwrap
import time

from ray_tpu.analysis import (baseline_diff, load_baseline, run_passes,
                              save_baseline)
from ray_tpu.analysis.baseline import baseline_path
from ray_tpu.analysis.cli import lint
from ray_tpu.analysis.core import ModuleSource
from ray_tpu.analysis.passes_concurrency import LockDisciplinePass, RpcAckPass
from ray_tpu.analysis.passes_growth import UnboundedGrowthPass
from ray_tpu.analysis.passes_jax import HostSyncPass, JitHygienePass
from ray_tpu.analysis.passes_tests import Tier1MarksPass


def _run(pass_, src, relpath="ray_tpu/core/mod.py"):
    module = ModuleSource("/repo/" + relpath, relpath,
                          textwrap.dedent(src))
    return pass_.run(module)


# ---------------------------------------------------------------------------
# lock-discipline


def test_lock_discipline_flags_rpc_under_with_lock():
    findings = _run(LockDisciplinePass(), """
        class A:
            def f(self):
                with self._lock:
                    self.cp.call("ping", None, timeout=1.0)
        """)
    assert len(findings) == 1
    f = findings[0]
    assert f.pass_id == "lock-discipline" and f.symbol == "A.f"
    assert "call" in f.message and "_lock" in f.message


def test_lock_discipline_clean_when_rpc_moves_outside_lock():
    findings = _run(LockDisciplinePass(), """
        class A:
            def f(self):
                with self._lock:
                    msg = self._q.pop()
                self.cp.call("ping", msg, timeout=1.0)
        """)
    assert findings == []


def test_lock_discipline_propagates_through_self_calls():
    findings = _run(LockDisciplinePass(), """
        class A:
            def _emit(self):
                self.cp.notify("report", {})
            def f(self):
                with self._lock:
                    self._emit()
        """)
    assert len(findings) == 1
    assert "self._emit()" in findings[0].message


def test_lock_discipline_flags_acquire_release_style():
    findings = _run(LockDisciplinePass(), """
        class A:
            def f(self):
                self._mu.acquire()
                time.sleep(1.0)
                self._mu.release()
        """)
    assert len(findings) == 1
    assert "time.sleep" in findings[0].message


def test_lock_discipline_allows_condition_wait_and_notify():
    # the sanctioned CV pattern: wait/notify on the held condition
    findings = _run(LockDisciplinePass(), """
        class A:
            def f(self):
                with self._cv:
                    while not self._ready:
                        self._cv.wait(1.0)
                    self._cv.notify()
        """)
    assert findings == []


def test_lock_discipline_pragma_suppresses():
    findings = _run(LockDisciplinePass(), """
        class A:
            def f(self):
                with self._lock:
                    # graftlint: disable=lock-discipline
                    self.cp.call("ping", None)
        """)
    assert findings == []


def test_lock_discipline_def_line_pragma_covers_whole_function():
    findings = _run(LockDisciplinePass(), """
        class A:
            def f(self):  # graftlint: disable=lock-discipline
                with self._lock:
                    self.cp.call("a", None)
                    self.cp.call("b", None)
        """)
    assert findings == []


def test_metrics_flusher_regression_fixture():
    # the exact pre-fix shape of MetricsFlusher.flush (PR 8 bug class):
    # the injected send callable — an RPC — invoked inside _flush_lock
    findings = _run(LockDisciplinePass(), """
        class MetricsFlusher:
            def flush(self):
                with self._flush_lock:
                    while self._backlog:
                        try:
                            self._send(self._backlog[0])
                        except Exception:
                            break
                        self._backlog.pop(0)
        """, relpath="ray_tpu/util/metrics.py")
    assert len(findings) == 1
    assert findings[0].tag == "_send"


def test_metrics_flusher_production_fix_holds():
    # the committed fix keeps every _send outside _flush_lock — a fresh
    # run over the real file must produce no lock-discipline finding
    import ray_tpu.util as u
    import os
    path = os.path.join(os.path.dirname(u.__file__), "metrics.py")
    findings = [f for f in run_passes([path],
                                      passes=[LockDisciplinePass()])
                if f.symbol.startswith("MetricsFlusher")]
    assert findings == []


# ---------------------------------------------------------------------------
# rpc-ack


def test_rpc_ack_flags_one_way_notify():
    findings = _run(RpcAckPass(), """
        class Agent:
            def _on_worker_dead(self, info):
                self._pool.get(self.cp_addr).notify(
                    "worker_died", {"worker_id": info.worker_id})
        """)
    assert len(findings) == 1
    f = findings[0]
    assert f.tag == "notify:worker_died"
    assert f.symbol == "Agent._on_worker_dead"


def test_rpc_ack_object_moved_regression_fixture():
    # pre-fix _h_drain_objects shape: the owner's location table depends
    # on this message, yet it went out as a droppable one-way notify
    findings = _run(RpcAckPass(), """
        class Agent:
            def _h_drain_objects(self, body):
                self._pool.get(owner).notify(
                    "object_moved", {"object_id": oid})
        """)
    assert [f.tag for f in findings] == ["notify:object_moved"]


def test_rpc_ack_clean_for_acked_call_and_condition_notify():
    findings = _run(RpcAckPass(), """
        class Agent:
            def f(self):
                self._pool.get(addr).call("worker_died", {}, timeout=5.0)
                with self._cv:
                    self._cv.notify()
                self._cv.notify_all()
        """)
    assert findings == []


def test_rpc_ack_fire_and_forget_pragma():
    findings = _run(RpcAckPass(), """
        class Agent:
            def f(self):
                # graftlint: fire-and-forget
                self.cp.notify("report_resources", {})
        """)
    assert findings == []


# ---------------------------------------------------------------------------
# host-sync


def test_host_sync_flags_np_asarray_in_hot_method():
    findings = _run(HostSyncPass(), """
        class Engine:
            def _decode_step(self):
                toks = np.asarray(self._dev_toks)
                return toks
        """, relpath="ray_tpu/serve/llm/engine.py")
    assert len(findings) == 1
    assert findings[0].tag == "np.asarray"


def test_host_sync_exempts_harvest_and_other_modules():
    harvest = _run(HostSyncPass(), """
        class Engine:
            def _harvest_one(self):
                return np.asarray(self._dev_toks)
        """, relpath="ray_tpu/serve/llm/engine.py")
    other_module = _run(HostSyncPass(), """
        class Engine:
            def _decode_step(self):
                return np.asarray(x)
        """, relpath="ray_tpu/core/worker.py")
    assert harvest == [] and other_module == []


def test_host_sync_flags_item_and_block_until_ready():
    findings = _run(HostSyncPass(), """
        class Engine:
            def _step(self):
                v = logits.item()
                out.block_until_ready()
        """, relpath="ray_tpu/serve/llm/engine.py")
    assert sorted(f.tag for f in findings) == [".item()",
                                               "block_until_ready"]


def test_host_sync_flags_eager_stack_and_concatenate():
    findings = _run(HostSyncPass(), """
        class Engine:
            def _flush_slot_patches(self, overrides):
                jnp = self._jnp
                vals = jnp.stack([jnp.asarray(v) for v in overrides])
                return jnp.concatenate([vals, self._pad])
        """, relpath="ray_tpu/serve/llm/engine.py")
    assert sorted(f.tag for f in findings) == ["jnp.concatenate",
                                               "jnp.stack"]
    assert all("eager device op" in f.message for f in findings)


def test_host_sync_flags_tuple_unpacked_random_split():
    src = """
        class Engine:
            def _prefill(self, req):
                self._rng, sub = %s(self._rng)
                return sub
        """
    for call in ("self._jax.random.split", "jax.random.split",
                 "random.split"):
        findings = _run(HostSyncPass(), src % call,
                        relpath="ray_tpu/serve/llm/engine.py")
        assert [f.tag for f in findings] == ["random.split"], call
    # one jitted two-output program is the way; numpy is not jnp; a split
    # kept whole is the caller's to slice where it may
    clean = _run(HostSyncPass(), """
        class Engine:
            def _prefill(self, req):
                self._rng, sub = self._split_key(self._rng)
                both = jax.random.split(sub)
                idx = np.concatenate([a, b])
                head, tail = text.split(",")
                return np.stack([idx, idx]), both
        """, relpath="ray_tpu/serve/llm/engine.py")
    assert clean == []


def test_host_sync_covers_arm_slot():
    findings = _run(HostSyncPass(), """
        class Engine:
            def _arm_slot(self, req, tok_dev):
                self._overrides[req.slot] = int(tok_dev[0])
        """, relpath="ray_tpu/serve/llm/engine.py")
    assert [f.tag for f in findings] == ["int(x[...])"]


# ---------------------------------------------------------------------------
# jit-hygiene


def test_jit_hygiene_flags_mutable_self_attr_read():
    findings = _run(JitHygienePass(), """
        import jax
        class Eng:
            def __init__(self):
                self._decode = jax.jit(self._decode_impl)
            def _decode_impl(self, x):
                return x + self._offset
            def bump(self):
                self._offset = 1
        """)
    assert [f.tag for f in findings] == ["self._offset"]


def test_jit_hygiene_flags_mutable_global_read():
    findings = _run(JitHygienePass(), """
        import jax
        cfg = {"scale": 2}
        @jax.jit
        def f(a):
            return a * cfg["scale"]
        """)
    assert [f.tag for f in findings] == ["global:cfg"]


def test_jit_hygiene_flags_python_branch_on_traced_param():
    findings = _run(JitHygienePass(), """
        import jax
        @jax.jit
        def f(a, flag):
            if flag:
                return a
            return -a
        """)
    assert [f.tag for f in findings] == ["branch:flag"]


def test_jit_hygiene_static_argnums_and_shape_checks_are_clean():
    findings = _run(JitHygienePass(), """
        import jax
        g = jax.jit(lambda a, flag: a if flag else -a, static_argnums=(1,))
        @jax.jit
        def h(a):
            if a.shape[0] > 4:
                return a
            return -a
        """)
    assert findings == []


def test_jit_hygiene_init_only_attrs_are_clean():
    findings = _run(JitHygienePass(), """
        import jax
        class Eng:
            def __init__(self):
                self._dim = 8
                self._decode = jax.jit(self._decode_impl)
            def _decode_impl(self, x):
                return x + self._dim
        """)
    assert findings == []


# ---------------------------------------------------------------------------
# unbounded-growth


def test_unbounded_growth_flags_handler_fed_dict():
    findings = _run(UnboundedGrowthPass(), """
        class CP:
            def __init__(self):
                self._series = {}
            def _h_report(self, body):
                self._series[body["k"]] = body["v"]
        """)
    assert [f.tag for f in findings] == ["self._series"]
    assert "never caps" in findings[0].message


def test_unbounded_growth_clean_with_retraction_or_cap():
    retracted = _run(UnboundedGrowthPass(), """
        class CP:
            def __init__(self):
                self._series = {}
            def _h_report(self, body):
                self._series[body["k"]] = body["v"]
            def _on_worker_dead(self, wid):
                self._series.pop(wid, None)
        """)
    capped = _run(UnboundedGrowthPass(), """
        class CP:
            def __init__(self):
                self._log = []
            def _h_append(self, body):
                self._log.append(body)
                del self._log[:-200]
        """)
    assert retracted == [] and capped == []


def test_unbounded_growth_one_hop_reachability():
    findings = _run(UnboundedGrowthPass(), """
        class CP:
            def __init__(self):
                self._seen = set()
            def _h_event(self, body):
                self._record(body)
            def _record(self, body):
                self._seen.add(body["id"])
        """)
    assert [f.symbol for f in findings] == ["CP._record"]


def test_unbounded_growth_non_handler_growth_is_clean():
    findings = _run(UnboundedGrowthPass(), """
        class Builder:
            def __init__(self):
                self._parts = []
            def add_part(self, p):
                self._parts.append(p)
        """)
    assert findings == []


# ---------------------------------------------------------------------------
# tier1-marks (semantics beyond what test_tier1_guard.py asserts)


def test_tier1_marks_fixture_semantics():
    src = """
        import pytest

        def test_uses_chaos(cluster):
            k = NodeKiller(cluster)
            k.start()

        @pytest.mark.slow
        def test_marked_chaos(cluster):
            NodeKiller(cluster).start()

        def test_worker_killer_max_kills():
            pass

        def test_three_nodes(c):
            c.add_node(); c.add_node(); c.add_node()

        def test_two_nodes(c):
            c.add_node(); c.add_node()
        """
    module = ModuleSource("/repo/tests/test_x.py", "tests/test_x.py",
                          textwrap.dedent(src))
    findings = Tier1MarksPass().run(module)
    assert sorted((f.symbol, f.tag) for f in findings) == [
        ("test_three_nodes", "multi-node"),
        ("test_uses_chaos", "chaos"),
    ]
    # non-test files are out of scope entirely
    other = ModuleSource("/repo/tests/conftest.py", "tests/conftest.py",
                         textwrap.dedent(src))
    assert Tier1MarksPass().run(other) == []


# ---------------------------------------------------------------------------
# finding shape + baseline keys


def test_finding_format_and_dict():
    (f,) = _run(RpcAckPass(), """
        class A:
            def f(self):
                self.cp.notify("x", {})
        """)
    line = f.format()
    assert line.startswith(f"{f.path}:{f.line}: [rpc-ack] A.f:")
    assert "(fix: " in line
    d = f.to_dict()
    assert d["pass"] == "rpc-ack" and d["symbol"] == "A.f"
    assert d["line"] == f.line and d["key"] == f.key


def test_baseline_keys_are_line_number_free():
    src = """
        class A:
            def f(self):
                self.cp.notify("x", {})
        """
    (a,) = _run(RpcAckPass(), src)
    (b,) = _run(RpcAckPass(), "\n\n\n" + textwrap.dedent(src))
    assert a.line != b.line and a.key == b.key


def test_baseline_drift_both_directions(tmp_path):
    base_file = str(tmp_path / "baseline.json")
    findings = _run(RpcAckPass(), """
        class A:
            def f(self):
                self.cp.notify("x", {})
        """)
    save_baseline(findings, base_file)
    new, stale = baseline_diff(findings, base_file)
    assert new == [] and stale == []
    # direction 1: an un-baselined finding is new
    new, stale = baseline_diff([], base_file)
    assert new == [] and stale == [findings[0].key]
    # direction 2: a baselined-but-fixed finding is stale
    save_baseline([], base_file)
    new, stale = baseline_diff(findings, base_file)
    assert [f.key for f in new] == [findings[0].key] and stale == []


def test_baseline_save_preserves_justifications(tmp_path):
    base_file = str(tmp_path / "baseline.json")
    findings = _run(RpcAckPass(), """
        class A:
            def f(self):
                self.cp.notify("x", {})
        """)
    save_baseline(findings, base_file)
    doc = json.loads(open(base_file).read())
    key = findings[0].key
    doc["entries"][key] = "because reasons"
    with open(base_file, "w") as fh:
        json.dump(doc, fh)
    save_baseline(findings, base_file)
    assert load_baseline(base_file)[key] == "because reasons"


# ---------------------------------------------------------------------------
# the tier-1 gate: full package vs the committed baseline, under budget


def test_package_run_matches_committed_baseline_exactly():
    t0 = time.monotonic()
    findings = run_passes()
    elapsed = time.monotonic() - t0
    assert elapsed < 15.0, f"graftlint full-package run took {elapsed:.1f}s"
    new, stale = baseline_diff(findings)
    assert not new, (
        "new graftlint findings — fix them, pragma the site with a "
        "justification, or `ray-tpu lint --baseline` and justify:\n  "
        + "\n  ".join(f.format() for f in new))
    assert not stale, (
        "stale GRAFTLINT_BASELINE.json entries (finding fixed but entry "
        "kept) — prune via `ray-tpu lint --baseline`:\n  "
        + "\n  ".join(stale))


def test_committed_baseline_entries_are_justified():
    base = load_baseline()
    assert base, f"missing baseline at {baseline_path()}"
    unjustified = [k for k, why in base.items() if not why.strip()]
    assert not unjustified, (
        "baseline entries need a one-line justification:\n  "
        + "\n  ".join(unjustified))


# ---------------------------------------------------------------------------
# CLI


def test_cli_json_document(tmp_path):
    out = io.StringIO()
    rc = lint(json_out=True, out=out)
    doc = json.loads(out.getvalue())
    assert rc == 0
    assert doc["new"] == [] and doc["stale_baseline_keys"] == []
    assert doc["parse_errors"] == []
    assert set(doc["passes"]) == {"lock-discipline", "rpc-ack", "host-sync",
                                  "jit-hygiene", "unbounded-growth"}
    for f in doc["findings"]:
        assert f["baselined"] is True


def test_cli_fails_on_new_finding(tmp_path):
    bad = tmp_path / "mod.py"
    bad.write_text(textwrap.dedent("""
        class A:
            def f(self):
                self.cp.notify("x", {})
        """))
    base_file = str(tmp_path / "baseline.json")
    out = io.StringIO()
    rc = lint(paths=[str(bad)], baseline_file=base_file, out=out)
    assert rc == 1 and "1 new" in out.getvalue()
    # --baseline accepts it; the next run is green against that file
    rc = lint(paths=[str(bad)], baseline_file=base_file,
              write_baseline=True, out=io.StringIO())
    assert rc == 0
    rc = lint(paths=[str(bad)], baseline_file=base_file, out=io.StringIO())
    assert rc == 0
