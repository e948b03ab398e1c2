"""Parallelism layer tests on the 8-device virtual CPU mesh.

Covers what the reference delegates or lacks (SURVEY.md §2.3, §5.7): ring/
Ulysses context parallelism, GPipe pipeline (fwd+grad), MoE expert parallel,
FSDP sharding inference, mesh construction.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.parallel.expert import moe_layer, moe_layer_tokens_sharded, top_k_gating
from ray_tpu.parallel.mesh import AXIS_ORDER, MeshSpec, build_mesh, validate_spec_for_slice
from ray_tpu.parallel.pipeline import pipeline_apply, stack_stage_params
from ray_tpu.parallel.ring_attention import ring_attention, ulysses_attention
from ray_tpu.parallel.sharding import (
    batch_sharding,
    infer_fsdp_sharding,
    logical_to_shardings,
    num_dp_shards,
)


def dense_attention(q, k, v, causal=True):
    T = q.shape[1]
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * (q.shape[-1] ** -0.5)
    if causal:
        mask = jnp.tril(jnp.ones((T, T), bool))
        logits = jnp.where(mask[None, None], logits, -jnp.inf)
    p = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


@pytest.fixture(scope="module")
def qkv():
    B, T, H, D = 2, 64, 8, 16
    ks = jax.random.split(jax.random.key(0), 3)
    return tuple(jax.random.normal(k, (B, T, H, D), jnp.float32) for k in ks)


def test_mesh_spec_infer():
    spec = MeshSpec.infer(8, tensor=2)
    assert spec.tensor == 2 and spec.fsdp == 4 and spec.total_devices() == 8
    spec2 = MeshSpec.infer(8, tensor=2, fsdp=2)
    assert spec2.data == 2
    with pytest.raises(ValueError):
        MeshSpec.infer(8, tensor=3)


def test_build_mesh_axes(jax_cpu_mesh):
    mesh = build_mesh(MeshSpec(fsdp=4, tensor=2))
    assert mesh.axis_names == AXIS_ORDER
    assert mesh.shape["fsdp"] == 4 and mesh.shape["tensor"] == 2


def test_validate_spec_for_slice():
    validate_spec_for_slice(MeshSpec(data=4, tensor=8), ici_devices=8)
    with pytest.raises(ValueError):
        validate_spec_for_slice(MeshSpec(tensor=16), ici_devices=8)


def test_ring_attention_matches_dense(qkv):
    q, k, v = qkv
    mesh = build_mesh(MeshSpec(context=8))
    ref = dense_attention(q, k, v, causal=True)
    out = ring_attention(q, k, v, mesh, causal=True)
    np.testing.assert_allclose(out, ref, atol=2e-5)
    ref_nc = dense_attention(q, k, v, causal=False)
    out_nc = ring_attention(q, k, v, mesh, causal=False)
    np.testing.assert_allclose(out_nc, ref_nc, atol=2e-5)


def test_ring_attention_grads(qkv):
    q, k, v = qkv
    mesh = build_mesh(MeshSpec(context=8))

    def loss_ring(q, k, v):
        return jnp.mean(ring_attention(q, k, v, mesh) ** 2)

    def loss_dense(q, k, v):
        return jnp.mean(dense_attention(q, k, v) ** 2)

    g1 = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(a, b, atol=2e-5)


def test_ulysses_attention_matches_dense(qkv):
    q, k, v = qkv
    mesh = build_mesh(MeshSpec(context=8))
    ref = dense_attention(q, k, v, causal=True)
    out = ulysses_attention(q, k, v, mesh, causal=True)
    np.testing.assert_allclose(out, ref, atol=2e-5)


def test_pipeline_forward_and_grad():
    mesh = build_mesh(MeshSpec(pipeline=4), jax.devices()[:4])
    D = 8

    def init(r, i):
        return {"w": jax.random.normal(r, (D, D)) * 0.3}

    params = stack_stage_params(init, 4, jax.random.key(1))

    def stage_fn(p, x):
        return jnp.tanh(x @ p["w"])

    x = jax.random.normal(jax.random.key(2), (16, D))
    out = pipeline_apply(stage_fn, params, x, mesh, num_microbatches=8)
    ref = x
    for s in range(4):
        ref = jnp.tanh(ref @ params["w"][s])
    np.testing.assert_allclose(out, ref, atol=1e-6)

    def loss_pp(params):
        return jnp.mean(pipeline_apply(stage_fn, params, x, mesh,
                                       num_microbatches=8) ** 2)

    def loss_seq(params):
        r = x
        for s in range(4):
            r = jnp.tanh(r @ params["w"][s])
        return jnp.mean(r ** 2)

    g1 = jax.grad(loss_pp)(params)["w"]
    g2 = jax.grad(loss_seq)(params)["w"]
    np.testing.assert_allclose(g1, g2, atol=1e-6)


def _moe_fixture():
    E, D = 8, 16
    ep = {"w1": jax.random.normal(jax.random.key(3), (E, D, 32)) * 0.3,
          "w2": jax.random.normal(jax.random.key(4), (E, 32, D)) * 0.3}
    gate_w = jax.random.normal(jax.random.key(5), (D, E)) * 0.3

    def expert_fn(p, tok):
        return jax.nn.relu(tok @ p["w1"]) @ p["w2"]

    x = jax.random.normal(jax.random.key(6), (8, 32, D))

    def dense_ref(x):
        toks = x.reshape(-1, D)
        probs, idx = top_k_gating(toks @ gate_w, 2)
        ref = jnp.zeros_like(toks)
        for slot in range(2):
            for e in range(E):
                m = idx[:, slot] == e
                one = {"w1": ep["w1"][e], "w2": ep["w2"][e]}
                ref = ref + jnp.where(m[:, None],
                                      probs[:, slot][:, None] * expert_fn(one, toks),
                                      0.0)
        return ref.reshape(x.shape)

    return E, ep, gate_w, expert_fn, x, dense_ref(x)


def test_moe_expert_parallel():
    E, ep, gate_w, expert_fn, x, ref = _moe_fixture()
    mesh = build_mesh(MeshSpec(expert=8))
    out = moe_layer(x, gate_w, expert_fn, ep, mesh, num_experts=E,
                    capacity_factor=8.0)
    np.testing.assert_allclose(out, ref, atol=1e-5)


def test_moe_tokens_sharded():
    E, ep, gate_w, expert_fn, x, ref = _moe_fixture()
    mesh = build_mesh(MeshSpec(expert=8))
    out = moe_layer_tokens_sharded(x, gate_w, expert_fn, ep, mesh,
                                   num_experts=E, capacity_factor=8.0)
    np.testing.assert_allclose(out, ref, atol=1e-5)


def test_infer_fsdp_sharding():
    mesh = build_mesh(MeshSpec(fsdp=8))
    shapes = {
        "big": jax.ShapeDtypeStruct((128, 64), jnp.float32),
        "odd": jax.ShapeDtypeStruct((7, 5), jnp.float32),
        "scalar": jax.ShapeDtypeStruct((), jnp.float32),
    }
    sh = infer_fsdp_sharding(shapes, mesh)
    assert sh["big"].spec == jax.sharding.PartitionSpec("fsdp")
    assert sh["odd"].spec == jax.sharding.PartitionSpec()
    assert sh["scalar"].spec == jax.sharding.PartitionSpec()


def test_sharded_matmul_runs_on_mesh():
    """End-to-end: params FSDP-sharded, batch data-sharded, jit runs."""
    mesh = build_mesh(MeshSpec(data=2, fsdp=4))
    w = jnp.ones((64, 32))
    x = jnp.ones((16, 64))
    w_sh = jax.device_put(w, infer_fsdp_sharding(
        jax.ShapeDtypeStruct(w.shape, w.dtype), mesh))
    x_sh = jax.device_put(x, batch_sharding(mesh, extra_dims=1))

    @jax.jit
    def f(w, x):
        return x @ w

    out = f(w_sh, x_sh)
    assert out.shape == (16, 32)
    np.testing.assert_allclose(np.asarray(out), np.full((16, 32), 64.0))
    assert num_dp_shards(mesh) == 8


def test_logical_rules():
    mesh = build_mesh(MeshSpec(fsdp=4, tensor=2))
    tree = {"wq": ("embed", "heads"), "bias": (None,)}
    sh = logical_to_shardings(tree, mesh)
    assert sh["wq"].spec == jax.sharding.PartitionSpec("fsdp", "tensor")
    assert sh["bias"].spec == jax.sharding.PartitionSpec()


def test_chunked_cross_entropy_matches_full():
    """Every chunk size (including non-divisors of T-1 — the padded-tail
    path) must reproduce the unchunked loss."""
    import numpy as np

    from ray_tpu.models import llama

    cfg = llama.llama_tiny()
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 64)), jnp.int32)
    inputs, targets = tokens[:, :-1], tokens[:, 1:]  # T-1 = 63
    hidden = llama.hidden_states(params, inputs, cfg)
    logits = (hidden @ params["lm_head"]).astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    full = -jnp.mean(
        jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0])
    grad_ref = None
    for chunk in (16, 63, 200):
        for remat in (True, False):
            c = llama.chunked_cross_entropy(
                params["lm_head"], hidden, targets, chunk=chunk, remat=remat)
            assert abs(float(c - full)) < 1e-4, (chunk, remat)
            # both remat modes must produce identical lm_head gradients
            # (remat only changes WHEN logits exist, never the math)
            g = jax.grad(lambda w: llama.chunked_cross_entropy(
                w, hidden, targets, chunk=chunk, remat=remat))(
                params["lm_head"])
            if grad_ref is None:
                grad_ref = g
            else:
                assert jnp.allclose(g, grad_ref, atol=1e-5), (chunk, remat)


def test_default_optimizer_names():
    from ray_tpu.train import spmd

    spmd.default_optimizer(name="adamw")
    spmd.default_optimizer(name="adafactor")
    with pytest.raises(ValueError):
        spmd.default_optimizer(name="lion")


def test_dryrun_collective_accounting(jax_cpu_mesh):
    """Per-axis collective accounting (VERDICT r3 item 9): each parallelism
    axis must insert its signature collective into the compiled HLO —
    tp: all-reduce; sp(context ring) and pp: collective-permute — and the
    accounting helper must see them."""
    import os
    import sys as _sys
    sys_path_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if sys_path_root not in _sys.path:
        _sys.path.insert(0, sys_path_root)
    import importlib
    graft = importlib.import_module("__graft_entry__")

    import jax
    import jax.numpy as jnp
    import numpy as np
    from ray_tpu.models import llama
    from ray_tpu.train import spmd

    # tp=2 x sp=2 x dp=2 llama train step
    mesh = build_mesh(MeshSpec(data=2, tensor=2, context=2))
    cfg = llama.llama_tiny(n_heads=4, n_kv_heads=2, attn_impl="ring")
    opt = spmd.default_optimizer(warmup_steps=1, decay_steps=10)
    state, sh = spmd.sharded_create_state(
        lambda: llama.init_params(jax.random.PRNGKey(0), cfg), opt, mesh,
        params_logical_axes=llama.logical_axes(cfg))
    step = spmd.make_train_step(
        lambda p, b: llama.loss_fn(p, b, cfg, mesh), opt, mesh, sh)
    tokens = jnp.asarray(np.zeros((2, 33), np.int32))
    batch = spmd.shard_batch({"tokens": tokens}, mesh)
    hlo = step.lower(state, batch).compile().as_text()
    counts = graft.collective_counts(hlo)
    assert counts.get("all-reduce", 0) > 0, counts          # tp + dp grads
    assert counts.get("collective-permute", 0) > 0, counts  # sp ring

    # pp=2 pipeline: ppermute ring between stages
    from ray_tpu.parallel.pipeline import pipeline_apply
    mesh_p = build_mesh(MeshSpec(data=4, pipeline=2))
    from jax.sharding import NamedSharding, PartitionSpec as P
    params = jax.device_put(
        {"w": jnp.zeros((2, 8, 8)), "b": jnp.zeros((2, 8))},
        NamedSharding(mesh_p, P("pipeline")))
    x = jnp.zeros((8, 8))

    def pp_fn(params, x):
        return pipeline_apply(lambda p, h: jnp.tanh(h @ p["w"] + p["b"]),
                              params, x, mesh_p, num_microbatches=4).sum()

    hlo_p = jax.jit(pp_fn).lower(params, x).compile().as_text()
    counts_p = graft.collective_counts(hlo_p)
    assert counts_p.get("collective-permute", 0) > 0, counts_p


def test_int8_matmul_close_and_differentiable():
    """int8_matmul (dynamic-quant MXU path): forward close
    to the fp matmul at int8 precision; gradients flow (straight-through)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from ray_tpu.models.llama import int8_matmul

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((32, 64)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((64, 48)), jnp.float32)
    out = int8_matmul(x, w)
    ref = x @ w
    # per-tensor int8: ~1% relative error at these magnitudes
    err = float(jnp.abs(out - ref).max() / jnp.abs(ref).max())
    assert err < 0.05, err

    def loss(x, w):
        return (int8_matmul(x, w) ** 2).mean()

    gx, gw = jax.grad(loss, argnums=(0, 1))(x, w)
    assert float(jnp.abs(gx).max()) > 0 and float(jnp.abs(gw).max()) > 0
    # straight-through backward matches the fp backward at quant precision
    gx_ref, gw_ref = jax.grad(lambda x, w: ((x @ w) ** 2).mean(),
                              argnums=(0, 1))(x, w)
    assert float(jnp.abs(gx - gx_ref).max() / jnp.abs(gx_ref).max()) < 0.1


# ---- partition-rule machinery (ISSUE 20: shared by train + serve) ------


def test_match_partition_rules_first_match_wins_and_scalars():
    from jax.sharding import PartitionSpec as P

    from ray_tpu.parallel.sharding import match_partition_rules

    params = {
        "layers": {"attn": {"wq": jnp.zeros((2, 8, 4, 2)),
                            "wo": jnp.zeros((2, 4, 2, 8))},
                   "mlp": {"w_up": jnp.zeros((2, 8, 16))}},
        "scale": jnp.zeros(()),          # scalar -> P() without any rule
        "final_norm": jnp.zeros((8,)),
    }
    rules = (
        (r"attn/wq$", P(None, None, "tensor", None)),
        # tuple specs are accepted and coerced to PartitionSpec
        (r"attn/", (None, "tensor", None, None)),
        (r".*", P()),
    )
    specs = match_partition_rules(rules, params)
    # first match wins: wq hits its dedicated rule, not the attn/ catch
    assert specs["layers"]["attn"]["wq"] == P(None, None, "tensor", None)
    assert specs["layers"]["attn"]["wo"] == P(None, "tensor", None, None)
    assert specs["layers"]["mlp"]["w_up"] == P()
    assert specs["scale"] == P()
    assert specs["final_norm"] == P()


def test_match_partition_rules_unmatched_raises():
    from ray_tpu.parallel.sharding import match_partition_rules

    with pytest.raises(ValueError, match="layers/mystery"):
        match_partition_rules(
            ((r"attn", jax.sharding.PartitionSpec()),),
            {"layers": {"mystery": jnp.zeros((4, 4))}})


def test_prune_spec_drops_dead_mesh_axes(jax_cpu_mesh):
    from jax.sharding import PartitionSpec as P

    from ray_tpu.parallel.sharding import prune_spec

    mesh = build_mesh(MeshSpec(fsdp=4, tensor=2))
    # present axes survive, absent names and size-1 axes drop, trailing
    # Nones are trimmed
    assert prune_spec(P("tensor", None, "fsdp"), mesh) == \
        P("tensor", None, "fsdp")
    assert prune_spec(P("tensor", "data"), mesh) == P("tensor")
    assert prune_spec(P(None, "data", None), mesh) == P()


def test_rule_shardings_places_params(jax_cpu_mesh):
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from ray_tpu.parallel.sharding import rule_shardings

    mesh = build_mesh(MeshSpec(tensor=2))
    params = {"layers": {"attn": {"wq": jnp.zeros((2, 8, 4, 2))}},
              "final_norm": jnp.zeros((8,))}
    rules = ((r"attn/wq$", P(None, None, "tensor", None)), (r".*", P()))
    sh = rule_shardings(rules, params, mesh)
    assert isinstance(sh["layers"]["attn"]["wq"], NamedSharding)
    placed = jax.device_put(params, sh)
    wq = placed["layers"]["attn"]["wq"]
    # the tensor axis really splits: each shard holds half the q heads
    assert wq.sharding.shard_shape(wq.shape) == (2, 8, 2, 2)
    assert placed["final_norm"].sharding.shard_shape((8,)) == (8,)


def test_serve_and_train_share_rule_machinery():
    """train/spmd.py's partition_rules path and the serve engine's TP
    rules both resolve through parallel.sharding.match_partition_rules —
    one implementation (ISSUE 20 satellite), no serve-side fork."""
    import inspect

    from ray_tpu.parallel import sharding as shd
    from ray_tpu.serve.llm.engine import LLMEngine
    from ray_tpu.train import spmd

    src = inspect.getsource(spmd.state_shardings)
    assert "rule_shardings" in src
    eng_src = inspect.getsource(LLMEngine._setup_tp_mesh)
    assert "rule_shardings" in eng_src
    # and the serve rules themselves are resolvable by the shared matcher
    from ray_tpu.models.llama import (init_params, llama_tiny,
                                      serve_partition_rules)
    assert "serve_partition_rules" in eng_src
    params = init_params(jax.random.PRNGKey(0), llama_tiny())
    specs = shd.match_partition_rules(serve_partition_rules(), params)
    flat = jax.tree_util.tree_leaves(
        specs, is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))
    assert all(isinstance(s, jax.sharding.PartitionSpec) for s in flat)


# ---- the layer scan's q / k / v weights, gathered one layer ahead -----------

def _plain_loss(params, batch, cfg, mesh):
    """llama.loss_fn with the layer scan in its plain form (the parent's
    _layer_fwd): every layer projects with its own wq / wk / wv as the
    parameters hold them, nothing rides in the carry, and autodiff alone
    makes the backward. The reference for what models/llama.py does."""
    from jax.ad_checkpoint import checkpoint_name

    from ray_tpu.models import llama
    tokens = batch["tokens"]
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    b, t = inputs.shape
    x = params["embed"][inputs].astype(cfg.dtype)
    cos, sin = llama.rope_freqs(cfg, jnp.broadcast_to(jnp.arange(t), (b, t)))

    def body(x, layer):
        h = llama.rms_norm(x, layer["attn_norm"], cfg.norm_eps)
        q, k, v = (checkpoint_name(
            jnp.einsum("btd,dhk->bthk", h, layer["attn"][w]), name)
            for w, name in (("wq", "q_proj"), ("wk", "k_proj"),
                            ("wv", "v_proj")))
        q, k = llama.apply_rope(q, cos, sin), llama.apply_rope(k, cos, sin)
        attn = checkpoint_name(llama._attention(q, k, v, cfg, mesh), "attn")
        x = x + checkpoint_name(
            jnp.einsum("bthk,hkd->btd", attn, layer["attn"]["wo"]),
            "attn_out")
        h = checkpoint_name(
            llama.rms_norm(x, layer["mlp_norm"], cfg.norm_eps), "mlp_in")
        gate = jax.nn.silu(h @ layer["mlp"]["w_gate"])
        return x + checkpoint_name(
            (gate * (h @ layer["mlp"]["w_up"])) @ layer["mlp"]["w_down"],
            "mlp_out"), None

    if cfg.remat:
        body = llama._remat(body, cfg)
    x, _ = jax.lax.scan(body, x, params["layers"])
    hidden = llama.rms_norm(x, params["final_norm"], cfg.norm_eps)
    return llama.chunked_cross_entropy(
        params["lm_head"], hidden, targets, chunk=cfg.ce_chunk,
        remat=cfg.ce_remat)


AHEAD_MESHES = {"fsdp4": dict(fsdp=4, data=2), "fsdp1": dict(tensor=2, data=4),
                "fsdp2_tensor2": dict(fsdp=2, tensor=2, data=2), "none": None}


@pytest.mark.parametrize("policy", ["dots", "full", "hybrid", "outs", None])
@pytest.mark.parametrize("mesh_name", list(AHEAD_MESHES))
def test_weights_gathered_ahead_change_no_loss_and_no_gradient(
        mesh_name, policy, jax_cpu_mesh):
    """float32 on the 8-device mesh: loss and every gradient leaf of
    llama.loss_fn equal the plain formulation's to 1e-6, where "fsdp"
    splits the weights (the gathered copies ride in the carry, their
    cotangent comes back through it and is summed by the ring), where it
    does not (no collective is named) and without a mesh; under every
    remat policy and without remat."""
    from ray_tpu.models import llama

    cfg = llama.llama_tiny(
        n_layers=3, max_seq_len=32, attn_impl="flash",
        remat=policy is not None, remat_policy=policy or "full")
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    mesh = None
    if AHEAD_MESHES[mesh_name]:
        mesh = build_mesh(MeshSpec(**AHEAD_MESHES[mesh_name]),
                          jax_cpu_mesh[:8])
        params = jax.device_put(params, logical_to_shardings(
            llama.logical_axes(cfg), mesh))
    batch = {"tokens": jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab_size, (8, 33)), jnp.int32)}
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: llama.loss_fn(p, batch, cfg, mesh)))(params)
    want, want_grads = jax.jit(jax.value_and_grad(
        lambda p: _plain_loss(p, batch, cfg, mesh)))(params)
    assert abs(float(loss) - float(want)) < 1e-6
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(grads),
                            jax.tree.leaves(want_grads)):
        assert g.shape == w.shape and float(jnp.max(jnp.abs(w))) > 0, path
        np.testing.assert_allclose(g, w, atol=1e-6, rtol=0, err_msg=str(path))


@pytest.mark.parametrize("policy", ["dots", "hybrid"])
def test_gathered_weights_are_no_residual_of_the_layer_scan(policy):
    """What the checkpointed scan keeps for the backward under the policies
    that save q / k / v: no array of a gathered weight's size a layer (the
    carry would be 50 MB x 24 at the train cell's widths). The residual is
    the shard. (`full` and `outs` save no q / k / v, so their recompute
    reads the gathered copy and it is kept: PERF.md section 7.)"""
    from ray_tpu.models import llama

    cfg = llama.llama_tiny(n_layers=3, max_seq_len=32, remat=True,
                           remat_policy=policy)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    batch = {"tokens": jnp.zeros((2, 33), jnp.int32)}
    packed = (cfg.dim, cfg.n_kv_heads,
              cfg.n_heads // cfg.n_kv_heads + 2, cfg.head_dim)
    _, vjp = jax.vjp(lambda p: llama.loss_fn(p, batch, cfg, None), params)
    saved = [x.shape for x in jax.tree.leaves(vjp) if hasattr(x, "shape")]
    assert saved and not [s for s in saved if s[-4:] == packed and
                          len(s) > 4 and cfg.n_layers in s[:-4]], saved
