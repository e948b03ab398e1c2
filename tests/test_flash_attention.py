"""The flash kernels of ray_tpu/ops/attention.py, held directly: forward and
jax.grad against XLA's attention in float32 `highest`, in the Pallas
interpreter on the CPU, over head layouts, lengths, dtypes, masks and block
sizes; what the kernels feed the MXU; that the model hands them K and V
unexpanded; and (the on-chip-measurement guide's third rehearsal) that they
compile for a described v5e at the train cell's shapes, which the interpreter
cannot tell: it knows neither tiling nor VMEM."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import llama
from ray_tpu.ops import attention as fa

HEADS = [(4, 4), (8, 2), (8, 1)]


def _qkv(h, hkv, t, dtype, d=32, b=2, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (b, t, h, d), dtype),
            jax.random.normal(ks[1], (b, t, hkv, d), dtype),
            jax.random.normal(ks[2], (b, t, hkv, d), dtype))


def _reference(q, k, v, causal=True):
    """XLA's attention on float32 copies, every head given its KV head."""
    n_rep = q.shape[2] // k.shape[2]
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    with jax.default_matmul_precision("highest"):
        return fa.reference_attention(q, jnp.repeat(k, n_rep, axis=2),
                                      jnp.repeat(v, n_rep, axis=2),
                                      causal=causal)


def _loss(attn):
    return lambda q, k, v: jnp.sum(attn(q, k, v).astype(jnp.float32) ** 2)


def _assert_close(got, want, dtype):
    tol = 1e-4 if dtype == jnp.float32 else 4e-2
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert g.shape == w.shape and g.dtype == dtype
        err = float(jnp.max(jnp.abs(g.astype(jnp.float32) - w)))
        assert err <= tol * max(1.0, float(jnp.max(jnp.abs(w)))), err


@pytest.mark.parametrize("blocks", [None, (64, 32)],
                         ids=["default_blocks", "blocks_64x32"])
@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("t", [64, 256, 320])
@pytest.mark.parametrize("h,hkv", HEADS)
def test_forward_and_grad_match_reference(h, hkv, t, dtype, causal, blocks):
    q, k, v = _qkv(h, hkv, t, dtype)
    kw = {} if blocks is None else {"block_q": blocks[0],
                                    "block_k": blocks[1]}
    attn = lambda q, k, v: fa.flash_attention(q, k, v, causal=causal, **kw)
    ref = lambda q, k, v: _reference(q, k, v, causal)
    _assert_close(attn(q, k, v), ref(q, k, v), dtype)
    # dk and dv come back at the KV heads, summed over their query heads
    _assert_close(jax.grad(_loss(attn), argnums=(0, 1, 2))(q, k, v),
                  jax.grad(_loss(ref), argnums=(0, 1, 2))(q, k, v), dtype)


@pytest.mark.parametrize("h,hkv", HEADS)
def test_default_blocks_loop_over_several_blocks(h, hkv):
    """T = 1024 is two default blocks of 512: the k loop of the forward,
    the k and q loops of the backward and the masked / unmasked split at
    the sizes the chip runs."""
    assert fa.default_blocks(1024, 1024, 128) == ((512, 512),) * 2
    q, k, v = _qkv(h, hkv, 1024, jnp.float32, d=8, b=1)
    _assert_close(
        jax.grad(_loss(fa.flash_attention), argnums=(0, 1, 2))(q, k, v),
        jax.grad(_loss(_reference), argnums=(0, 1, 2))(q, k, v), jnp.float32)


@pytest.mark.parametrize("h,hkv", HEADS)
@pytest.mark.parametrize("wrap", ["jit", "shard_map"])
def test_under_jit_and_shard_map(wrap, h, hkv, jax_cpu_mesh):
    q, k, v = _qkv(h, hkv, 128, jnp.float32, b=4)
    grad = jax.grad(_loss(fa.flash_attention), argnums=(0, 1, 2))
    if wrap == "shard_map":
        from jax.sharding import Mesh, PartitionSpec as P
        tensor = 2 if hkv % 2 == 0 else 1
        mesh = Mesh(np.array(jax_cpu_mesh[:2 * tensor]).reshape(2, tensor),
                    ("data", "tensor"))
        spec = P("data", None, "tensor", None)
        attn = jax.shard_map(fa.flash_attention, mesh=mesh,
                             in_specs=(spec,) * 3, out_specs=spec,
                             check_vma=False)
        grad = jax.grad(_loss(attn), argnums=(0, 1, 2))
    _assert_close(jax.jit(grad)(q, k, v),
                  jax.grad(_loss(_reference), argnums=(0, 1, 2))(q, k, v),
                  jnp.float32)


@pytest.mark.parametrize("kw,match", [
    ({"block_q": 128}, "must divide"),
    ({"block_k": 192}, "must divide"),
])
def test_block_that_does_not_divide_raises(kw, match):
    q, k, v = _qkv(4, 4, 320, jnp.float32)
    with pytest.raises(ValueError, match=match):
        fa.flash_attention(q, k, v, **kw)


def test_sequence_past_vmem_raises_and_names_the_way_out():
    """The backward keeps a head's Q, dO, K, V, dq, dk and dv whole in
    VMEM: 32k tokens at D = 128 do not fit, and the error says so before
    any compile."""
    args = [jax.ShapeDtypeStruct((1, 32768, h, 128), jnp.bfloat16)
            for h in (8, 2, 2)]
    jax.eval_shape(fa.flash_attention, *args)
    with pytest.raises(ValueError, match="does not fit VMEM.*ring_attention"):
        jax.eval_shape(jax.grad(_loss(fa.flash_attention)), *args)


def test_query_heads_must_be_a_multiple_of_kv_heads():
    q, k, v = _qkv(4, 3, 64, jnp.float32)
    with pytest.raises(ValueError, match="multiple"):
        fa.flash_attention(q, k, v)


@pytest.mark.parametrize("t,d,want", [
    (2048, 128, 512),      # the train cell
    (64, 16, 64),          # shorter than a block: the sequence itself
    (320, 128, 320),
    (640, 128, 128),       # longer than a block: a multiple of 128 divides
    (2048, 256, 512),
])
def test_default_block_q_from_the_shapes(t, d, want):
    blocks = fa.default_blocks(t, t, d)
    assert all(bq == want for bq, _ in blocks)
    assert all(t % bq == 0 and t % bk == 0 for bq, bk in blocks)


def test_default_blocks_refuse_a_long_ragged_sequence():
    with pytest.raises(ValueError, match="multiple of 128"):
        fa.default_blocks(2048 + 64, 2048 + 64, 128)


# ---- what the kernels feed the MXU ----------------------------------------

def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs inside its equations
    (pjit, custom_vjp, shard_map, scan, pallas_call bodies ...)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for p in eqn.params.values():
            for sub in (p if isinstance(p, (list, tuple)) else [p]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub)


@pytest.mark.parametrize("which", ["forward", "backward"])
def test_bf16_kernels_hold_no_f32_product(which):
    q, k, v = _qkv(8, 2, 256, jnp.bfloat16)
    fn = fa.flash_attention if which == "forward" else jax.grad(
        _loss(fa.flash_attention), argnums=(0, 1, 2))
    kernels = [e for e in _eqns(jax.make_jaxpr(fn)(q, k, v).jaxpr)
               if e.primitive.name == "pallas_call"]
    assert [e.params["name"] for e in kernels] == (
        ["flash_fwd"] if which == "forward" else ["flash_fwd", "flash_bwd"])
    dots = [e for kern in kernels for e in _eqns(kern.params["jaxpr"])
            if e.primitive.name == "dot_general"]
    # forward 2 products, backward 5, each once per loop (masked and
    # unmasked) the kernel holds
    assert len(dots) == (4 if which == "forward" else 14)
    for e in dots:
        assert [x.aval.dtype for x in e.invars] == [jnp.bfloat16] * 2, e
        assert e.outvars[0].aval.dtype == jnp.float32


@pytest.mark.parametrize("impl", ["flash", "dense"])
def test_model_hands_the_kernels_k_and_v_unexpanded(impl):
    """llama.loss_fn with fewer KV heads than query heads: on the flash
    path no K / V of n_heads exists before the kernel and the kernels read
    [B * n_kv_heads, T, D]; the dense path still expands (which also shows
    that this test would see an expansion)."""
    cfg = llama.llama_tiny(attn_impl=impl, max_seq_len=32)
    assert cfg.n_kv_heads < cfg.n_heads
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    batch = {"tokens": jnp.zeros((2, 33), jnp.int32)}
    eqns = list(_eqns(jax.make_jaxpr(
        lambda p: llama.loss_fn(p, batch, cfg, None))(params).jaxpr))
    n_rep = cfg.n_heads // cfg.n_kv_heads
    expanded = (2, 32, cfg.n_kv_heads, n_rep, cfg.head_dim)
    expansions = [e for e in eqns if e.primitive.name == "broadcast_in_dim"
                  and e.outvars[0].aval.shape == expanded]
    assert len(expansions) == (0 if impl == "flash" else 2)
    kernels = [e for e in eqns if e.primitive.name == "pallas_call"]
    assert len(kernels) == (1 if impl == "flash" else 0)
    for kern in kernels:
        assert [x.aval.shape[0] for x in kern.invars] == [
            2 * cfg.n_heads, 2 * cfg.n_kv_heads, 2 * cfg.n_kv_heads]


def test_model_expands_where_kv_heads_do_not_split_over_tensor(jax_cpu_mesh):
    """Under a mesh whose tensor degree does not divide the KV heads a
    shard of query heads would not find its KV heads on its chip: the
    model then expands as before, and the loss is the dense one."""
    from ray_tpu.parallel.mesh import MeshSpec, build_mesh

    mesh = build_mesh(MeshSpec(fsdp=2, tensor=4), jax_cpu_mesh[:8])
    tokens = jnp.asarray(np.random.default_rng(0).integers(0, 256, (4, 33)),
                         jnp.int32)
    losses = {}
    for impl in ("dense", "flash"):
        cfg = llama.llama_tiny(attn_impl=impl, max_seq_len=32)  # 4 / 2 heads
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
        losses[impl] = float(jax.jit(
            lambda p, cfg=cfg: llama.loss_fn(p, {"tokens": tokens}, cfg,
                                             mesh))(params))
    assert abs(losses["flash"] - losses["dense"]) < 1e-4


# ---- compiled for the chip, without the chip ------------------------------

@pytest.fixture(scope="module")
def v5e_2x2():
    """The four devices of a described v5e 2x2. Described here and never at
    import: only one process may load the TPU's library, and every xdist
    worker imports this file."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a program compiled for a described chip is written to the persistent
    # cache and cannot be read back without one: keep it out
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo.devices
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(v5e_2x2):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(v5e_2x2[0])


@pytest.mark.parametrize("which", ["forward", "backward"])
def test_kernels_compile_for_v5e_at_the_train_cells_shapes(which, one_chip):
    """mistral7b-train-fsdp4, one chip's share: q [2, 2048, 32, 128], k and
    v [2, 2048, 8, 128], bf16, causal, default blocks."""
    q, kv = ((2, 2048, heads, 128) for heads in (32, 8))
    args = [jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=one_chip)
            for s in (q, kv, kv)]
    attn = lambda q, k, v: fa.flash_attention(q, k, v, interpret=False)
    fn = attn if which == "forward" else jax.grad(_loss(attn),
                                                  argnums=(0, 1, 2))
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert text.count("tpu_custom_call") >= (1 if which == "forward" else 2)


def _loop_bodies(text):
    """The scheduled while bodies of a compiled module, in the text's
    order: each a list of (instruction line) strings."""
    bodies, cur = [], None
    for line in text.split("\n"):
        if line.startswith("%") and line.rstrip().endswith("{"):
            cur = [] if "region" in line.split("(")[0] else None
        elif line.startswith("}"):
            if cur:
                bodies.append(cur)
            cur = None
        elif cur is not None:
            cur.append(line)
    return bodies


def test_train_step_for_v5e_awaits_no_qkv_weight_inside_its_own_layer(
        v5e_2x2, monkeypatch):
    """mistral7b-train-fsdp4's step at depth 2, the cell's widths and
    recipe, lowered on the described 2x2 with fsdp=4 and read as scheduled.
    What models/llama.py claims: (1) in the forward loop's body no
    collective stands under the q / k / v products (the parent's body sent
    each of wq / wk / wv round a ring of permutes there, started at its top
    with only the norm in front); the three weights arrive as ONE gather of
    the packed [1024, 8, 6, 128] shard, started in the body and consumed by
    the NEXT iteration (its result leaves through the loop's carry). (2) in
    the backward loop's body the weights' cotangent is summed by three hops
    of a [1024, 8, 6, 128] permute that read the carry, not by a ring under
    the products that made it; the only collectives left under the products
    are FSDP's second gather for dh. (3) no gathered [4096, 8, 6, 128] is
    stacked a layer as a residual."""
    import functools
    import re

    from jax.sharding import NamedSharding, PartitionSpec as P

    from ray_tpu.parallel.mesh import MeshSpec, build_mesh
    from ray_tpu.parallel.sharding import batch_sharding
    from ray_tpu.train import spmd

    monkeypatch.setattr(fa, "flash_attention", functools.partial(
        fa.flash_attention, interpret=False))
    cfg = llama.LlamaConfig(
        vocab_size=32768, dim=4096, n_layers=2, n_heads=32, n_kv_heads=8,
        ffn_dim=14336, max_seq_len=2048, rope_theta=1e6, dtype=jnp.bfloat16,
        remat_policy="dots", ce_chunk=2048, ce_remat=False,
        attn_impl="flash")
    mesh = build_mesh(MeshSpec(fsdp=4), v5e_2x2)
    opt = spmd.default_optimizer(name="adafactor")
    make = lambda: spmd.TrainState.create(
        llama.init_params(jax.random.PRNGKey(0), cfg), opt)
    shapes = jax.eval_shape(make)
    sh = spmd.state_shardings(llama.logical_axes(cfg), shapes.params, mesh,
                              opt)
    state = jax.tree.map(
        lambda s, h: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=h),
        shapes, sh)
    batch = {"tokens": jax.ShapeDtypeStruct(
        (8, 2049), jnp.int32, sharding=batch_sharding(mesh, extra_dims=1))}
    step = spmd.make_train_step(
        lambda p, b: llama.loss_fn(p, b, cfg, mesh), opt, mesh, sh)
    text = step.lower(state, batch).compile().as_text()

    assert "bf16[2,4096,8,6,128]" not in text          # (3)
    collective = re.compile(
        r" (collective-permute-start|all-gather|all-gather-start|"
        r"reduce-scatter|all-reduce)\(|async-collective-start")
    bodies = [b for b in _loop_bodies(text)
              if any("flash_fwd" in line for line in b)]
    backward = [b for b in bodies if any("flash_bwd" in line for line in b)]
    forward = [b for b in bodies if b not in backward]
    assert len(forward) == 1 and len(backward) == 1
    qkv = "btd,dhk->bthk"
    fwd = [line for line in forward[0] if collective.search(line)]
    assert fwd and not [line for line in fwd if qkv in line], fwd  # (1)
    gathers = [line for line in forward[0]
               if "= bf16[4096,8,6,128]" in line.replace("(", " ")
               and ("async-collective-done" in line or "all-gather" in line)]
    assert len(gathers) == 1, gathers
    bwd = [line for line in backward[0] if collective.search(line)]
    ring = [line for line in bwd if "bf16[1024,8,6,128]" in line
            and "collective-permute-start" in line and "ppermute" in line]
    assert len(ring) == 3, ring                                     # (2)
    under = [line for line in bwd if qkv in line]
    assert under and all("transpose(jvp(" in line for line in under), under


# ---- the paged kernel on a latent pool (ISSUE 44) ---------------------------
# One row a token, the first ``value_lanes`` lanes of which are its values,
# all heads on the one row: against a dense masked softmax, interpreted.

def _latent_case(seed, b, t, h, latent, value, pages, page=8, lanes=128,
                 dtype=jnp.float32):
    from ray_tpu.ops import paged_attention as paged_ops  # noqa: F401
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    n_pages = b * pages + 1
    rows = jax.random.normal(ks[0], (1, 1, n_pages, page, latent), dtype)
    pool = jnp.pad(rows, ((0, 0),) * 4 + ((0, lanes - latent),))
    tables = 1 + jnp.arange(b * pages, dtype=jnp.int32).reshape(b, pages)
    q = jax.random.normal(ks[1], (b, t, h, latent), dtype)
    base = jax.random.randint(ks[2], (b,), 0, pages * page - t).astype(
        jnp.int32)
    return q, pool, tables, base


def _latent_want(q, pool, tables, base, limit, latent, value, sm):
    """Dense masked softmax over each slot's rows, values = the rows'
    first ``value`` lanes, in float64 numpy."""
    import numpy as np
    q, pool = np.asarray(q, np.float64), np.asarray(pool, np.float64)
    b, t, h, _ = q.shape
    out = np.zeros((b, t, h, value))
    for i in range(b):
        rows = pool[0, 0, np.asarray(tables[i])].reshape(-1, pool.shape[-1])
        for j in range(t):
            pos = int(base[i]) + j
            n = min(pos + 1, int(limit[i]))
            s = q[i, j] @ rows[:n, :latent].T * sm            # [h, n]
            p = np.exp(s - s.max(-1, keepdims=True))
            out[i, j] = (p / p.sum(-1, keepdims=True)) @ rows[:n, :value]
    return out


@pytest.mark.parametrize("t,h", [(1, 4), (5, 4), (1, 32)],
                         ids=["decode", "verify_k+1", "decode_32_rows"])
def test_latent_pool_spans_match_a_dense_masked_softmax(t, h):
    from ray_tpu.ops import paged_attention as paged_ops
    latent, value, sm = 40, 32, 24 ** -0.5
    q, pool, tables, base = _latent_case(t + h, 3, t, h, latent, value, 6)
    got = paged_ops.paged_latent_attention(q, pool, tables, base, None, 0,
                                           sm_scale=sm, value_lanes=value)
    assert got.shape == (3, t, h, value)
    want = _latent_want(q, pool, tables, base, [48] * 3, latent, value, sm)
    np.testing.assert_allclose(got, want, atol=2e-5)
    if t == 1:
        one = paged_ops.paged_decode_attention(
            q[:, 0], pool, None, tables, base, 0, sm_scale=sm,
            value_lanes=value)
        np.testing.assert_array_equal(one, got[:, 0])


@pytest.mark.parametrize("c,h", [(16, 4), (128, 32)],
                         ids=["one_span", "cut_into_spans"])
def test_latent_pool_chunk_matches_a_dense_masked_softmax(c, h):
    """A chunk at ``start`` under ``true_len``; 128 positions x 32 heads is
    4,096 rows on the one KV head, cut into two spans of 64 positions."""
    from ray_tpu.ops import paged_attention as paged_ops
    latent, value, sm = 40, 32, 24 ** -0.5
    pages = (8 + c) // 8 + 1
    q, pool, tables, _ = _latent_case(c, 1, c, h, latent, value, pages)
    start, true_len = 8, 8 + c - 3
    got = paged_ops.paged_chunk_attention(
        q, pool, None, tables[0], jnp.int32(start), jnp.int32(true_len), 0,
        sm_scale=sm, value_lanes=value)
    want = _latent_want(q, pool, tables, [start], [true_len], latent, value,
                        sm)
    # (rows at or past true_len are padding: their output is not read)
    np.testing.assert_allclose(got[0, :c - 3], want[0, :c - 3], atol=2e-5)


def test_latent_pool_garbage_in_the_querys_padding_lanes_is_harmless():
    """The pool's padding lanes are zeros for ever (written so, never
    read as values: value_lanes <= latent), so whatever a query of the
    pool's full width holds there adds nothing to a score."""
    from ray_tpu.ops import paged_attention as paged_ops
    latent, value, sm = 40, 32, 24 ** -0.5
    q, pool, tables, base = _latent_case(11, 2, 2, 4, latent, value, 4)
    clean = paged_ops.paged_latent_attention(
        q, pool, tables, base, None, 0, sm_scale=sm, value_lanes=value)
    junk = 1e3 * jax.random.normal(jax.random.PRNGKey(5),
                                   q.shape[:3] + (128 - latent,))
    dirty = paged_ops.paged_latent_attention(
        jnp.concatenate([q, junk], axis=-1), pool, tables, base, None, 0,
        sm_scale=sm, value_lanes=value)
    np.testing.assert_array_equal(clean, dirty)


@pytest.mark.parametrize("which", ["decode", "verify", "chunk"])
def test_latent_kernels_compile_for_v5e_at_the_cells_shapes(which, one_chip):
    """joyai-llm-flash-serve-decode: a pool [5, 1, 2560, 128, 640] bf16 read
    ONCE (one kernel, no second stream of pages), 128 slots x 32 query rows
    of 576 lanes on one KV row, tables of 24 pages; a chunk of 512."""
    from ray_tpu.ops import paged_attention as paged_ops

    def s(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    pool = s((5, 1, 2560, 128, 640), jnp.bfloat16)
    kw = dict(sm_scale=192 ** -0.5, interpret=False, value_lanes=512)
    if which == "chunk":
        fn = lambda q, pool, pt, a, n, l: paged_ops.paged_chunk_attention(
            q, pool, None, pt, a, n, l, **kw)
        args = (s((1, 512, 32, 576), jnp.bfloat16), pool, s((24,)), s(()),
                s(()), s(()))
        out = (1, 512, 32, 512)
    else:
        kernel = paged_ops.paged_decode_attention if which == "decode" \
            else paged_ops.paged_verify_attention
        fn = lambda q, pool, pt, pos, l: kernel(q, pool, None, pt, pos, l,
                                                **kw)
        span = () if which == "decode" else (5,)
        args = (s((128,) + span + (32, 576), jnp.bfloat16), pool,
                s((128, 24)), s((128,)), s(()))
        out = (128,) + span + (32, 512)
    compiled = jax.jit(fn).lower(*args).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1
    assert compiled.out_info.shape == out


@pytest.mark.parametrize("which", ["decode_window", "decode_full",
                                   "chunk_window", "chunk_full"])
def test_lower_edge_calls_compile_for_v5e_at_the_cells_shapes(which, one_chip):
    """trinity-large-serve-longctx (ISSUE 52): the walking body with a lower
    edge, 24 slots x 48 query heads on 8 KV heads of 128; a window layer on
    the ring pool [4, 8, 889, 128, 128] through ring tables of 37 entries
    (window 4,096), the full layer on [1, 8, 2881, 128, 128] through tables
    of 128 pages, whose KV heads do not fit the scratch at once; a chunk of
    512. What interpret mode cannot show: the scratch fits VMEM and every
    slice is aligned to the tiling."""
    from ray_tpu.ops import paged_attention as paged_ops

    def s(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    ring = which.endswith("window")
    pool = s((4, 8, 889, 128, 128) if ring else (1, 8, 2881, 128, 128),
             jnp.bfloat16)
    width = 37 if ring else 128
    kw = dict(interpret=False, window=4096 if ring else 0)
    if which.startswith("chunk"):
        fn = lambda q, k, v, pt, a, n, l: paged_ops.paged_chunk_attention(
            q, k, v, pt, a, n, l, **kw)
        args = (s((1, 512, 48, 128), jnp.bfloat16), pool, pool, s((width,)),
                s(()), s(()), s(()))
        out = (1, 512, 48, 128)
    else:
        fn = lambda q, k, v, pt, pos, l: paged_ops.paged_decode_attention(
            q, k, v, pt, pos, l, **kw)
        args = (s((24, 48, 128), jnp.bfloat16), pool, pool, s((24, width)),
                s((24,)), s(()))
        out = (24, 48, 128)
    compiled = jax.jit(fn).lower(*args).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1
    assert compiled.out_info.shape == out
