"""What PR 21 (chip bring-up) fixed, held on the CPU tier: the compile-cache
rule, chip detection from device nodes, the array-deserialize gate that
keeps a driver off the chip, and chip_smoke.py refusing to run without one.
(Explicit "pallas" raising on un-tileable shapes lives in
test_paged_kernels.py; the engine-loop failure path in test_serve_llm.py.)
"""

import os
import subprocess
import sys
import time

import numpy as np

from ray_tpu.core import compile_cache
from ray_tpu.core.config import package_parent_path
from ray_tpu.parallel import topology

REPO = package_parent_path()


# ---- compile cache ------------------------------------------------------

def test_cache_dir_from_outside_is_respected_and_nothing_else_set():
    env = {"JAX_COMPILATION_CACHE_DIR": "/somewhere/else",
           "JAX_PLATFORMS": "tpu,cpu"}
    before = dict(env)
    assert compile_cache.configure(env) == "/somewhere/else"
    assert env == before


def test_cache_dir_defaults_inside_the_checkout_per_platform_and_stable():
    env = {"JAX_PLATFORMS": "tpu,cpu"}
    path = compile_cache.configure(env)
    assert path == os.path.join(REPO, ".jax_cache", "tpu")
    assert env["JAX_COMPILATION_CACHE_DIR"] == path
    # a second call, and a second process's environment, name the same
    # directory: no pid, no timestamp, nothing under /tmp
    assert compile_cache.configure(env) == path
    assert compile_cache.configure({"JAX_PLATFORMS": "tpu,cpu"}) == path
    # CPU test entries stay out of the directory a chip run reads
    assert compile_cache.configure({"JAX_PLATFORMS": "cpu"}) \
        == os.path.join(REPO, ".jax_cache", "cpu")
    assert compile_cache.configure({}) \
        == os.path.join(REPO, ".jax_cache", "default")


def test_test_suite_itself_uses_the_helper():
    """conftest configured this process: the suite's cache is the rule's
    directory (or the one given from outside), and jax reads that one."""
    import jax
    want = os.environ["JAX_COMPILATION_CACHE_DIR"]
    assert jax.config.jax_compilation_cache_dir == want
    assert not want.startswith("/tmp/ray_tpu_test_jit_cache")


# ---- chip detection -----------------------------------------------------

def _fake_dev(tmp_path, monkeypatch, vfio=(), accel=()):
    (tmp_path / "vfio").mkdir()
    for n in vfio:
        (tmp_path / "vfio" / str(n)).touch()
    (tmp_path / "vfio" / "vfio").touch()      # the control node is no chip
    for n in accel:
        (tmp_path / f"accel{n}").touch()
    monkeypatch.setattr(topology, "_CHIP_DEVICE_GLOBS", (
        str(tmp_path / "vfio" / "[0-9]*"), str(tmp_path / "accel[0-9]*")))
    for var in ("RAY_TPU_FAKE_TOPOLOGY", "TPU_ACCELERATOR_TYPE", "TPU_NAME",
                "TPU_WORKER_ID", "TPU_WORKER_HOSTNAMES", "TPU_TOPOLOGY"):
        monkeypatch.delenv(var, raising=False)


def test_chips_found_with_nothing_in_the_environment(tmp_path, monkeypatch):
    _fake_dev(tmp_path, monkeypatch, vfio=(0,))
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    assert topology.local_chip_count() == 1
    topo = topology.detect_local_topology()
    assert topo is not None and topo.chips_per_host == 1


def test_chip_count_is_what_is_there_not_a_table_default(tmp_path,
                                                         monkeypatch):
    """The chip machine says TPU_ACCELERATOR_TYPE=v5litepod-4 on a host
    with ONE chip: the TPU resource is the device nodes present."""
    _fake_dev(tmp_path, monkeypatch, vfio=(0,))
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    monkeypatch.setenv("TPU_ACCELERATOR_TYPE", "v5litepod-4")
    topo = topology.detect_local_topology()
    assert topo.chips_per_host == 1 and topo.pod_type == "v5litepod-4"
    (tmp_path / "vfio" / "1").touch()
    (tmp_path / "vfio" / "2").touch()
    (tmp_path / "vfio" / "3").touch()
    assert topology.detect_local_topology().chips_per_host == 4


def test_no_chips_without_device_nodes_or_when_pinned_to_cpu(tmp_path,
                                                             monkeypatch):
    _fake_dev(tmp_path, monkeypatch)
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    monkeypatch.setenv("TPU_ACCELERATOR_TYPE", "v5litepod-4")
    assert topology.detect_local_topology() is None     # env alone: no chip
    (tmp_path / "accel0").touch()
    assert topology.local_chip_count() == 1
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")          # chips unreachable
    assert topology.local_chip_count() == 0
    assert topology.detect_local_topology() is None


# ---- a driver that merely imported jax stays off the chip ----------------

def test_array_deserialize_does_not_initialise_a_backend(monkeypatch):
    import jax
    from jax._src import xla_bridge

    from ray_tpu.core import serialization

    host = np.arange(4, dtype=np.float32)
    # pinned to cpu (this suite): device_put cannot take a chip
    assert isinstance(serialization._restore_jax_array(
        host, "float32", True), jax.Array)
    # not pinned, backend untouched: stay on the host
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    monkeypatch.setattr(xla_bridge, "backends_are_initialized",
                        lambda: False)
    assert serialization._restore_jax_array(host, "float32", True) is host
    # the chip-holder itself (backend initialised) gets device arrays
    monkeypatch.setattr(xla_bridge, "backends_are_initialized",
                        lambda: True)
    assert isinstance(serialization._restore_jax_array(
        host, "float32", True), jax.Array)


# ---- chip_smoke.py ------------------------------------------------------

def test_chip_smoke_without_a_chip_exits_nonzero_at_once():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    t0 = time.monotonic()
    proc = subprocess.run([sys.executable, os.path.join(REPO,
                                                        "chip_smoke.py")],
                          env=env, cwd=REPO, capture_output=True, text=True,
                          timeout=60)
    assert proc.returncode != 0
    assert time.monotonic() - t0 < 20.0
    assert proc.stdout.strip() == ""                    # no result line
    assert "no CPU mode" in proc.stderr
    assert len(proc.stderr.strip().splitlines()) == 1   # one sentence


def test_chip_smoke_result_line_has_exactly_the_contract_keys(capsys):
    """The checker reads the LAST stdout line and wants exactly ``ok`` and
    ``device`` = ``platform``/``kind``/``count``; the detailed report is a
    separate, earlier line."""
    import importlib.util
    import json
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    mod.print_result(True, {"platform": "tpu", "kind": "TPU v5 lite",
                            "count": 1, "extra": "dropped"})
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(last) == {"ok": True, "device": {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1}}


# ---- flash attention under a multi-device mesh ---------------------------

def test_flash_attention_runs_per_shard_on_a_mesh(jax_cpu_mesh):
    """A compiled Pallas kernel cannot be partitioned by GSPMD (on the chip:
    "Mosaic kernels cannot be automatically partitioned"), which the CPU's
    interpret mode hides. So the model must wrap the flash kernel in a
    shard_map whenever the mesh has more than one device — checked in the
    traced program — and the sharded loss must equal the dense one."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import llama
    from ray_tpu.parallel.mesh import MeshSpec, build_mesh

    mesh = build_mesh(MeshSpec(fsdp=2, tensor=2), jax_cpu_mesh[:4])
    tokens = jnp.asarray(np.random.default_rng(0).integers(0, 256, (4, 33)),
                         jnp.int32)
    losses = {}
    for impl in ("dense", "flash"):
        cfg = llama.llama_tiny(attn_impl=impl, max_seq_len=32)
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
        fn = lambda p, cfg=cfg: llama.loss_fn(p, {"tokens": tokens}, cfg,
                                              mesh)
        losses[impl] = float(jax.jit(fn)(params))
        # (traced at the attention itself: the q / k / v products have a
        # shard_map of their own wherever "fsdp" splits the weights)
        qkv = [jnp.zeros((4, 32, h, cfg.head_dim))
               for h in (cfg.n_heads, cfg.n_kv_heads, cfg.n_kv_heads)]
        attn = lambda q, k, v, cfg=cfg: llama._attention(q, k, v, cfg, mesh)
        assert ("shard_map" in str(jax.make_jaxpr(attn)(*qkv))) \
            == (impl == "flash")
    assert abs(losses["flash"] - losses["dense"]) < 1e-4
