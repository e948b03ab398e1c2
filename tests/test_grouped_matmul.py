"""The routed layer's grouped product, walked by group (ISSUE 49 and 50;
ray_tpu/ops/grouped_matmul.py, parallel/expert.grouped_swiglu), interpreted
on the CPU at toy widths:

- a product alone is the megablox ``gmm``'s, bit for bit (the same k tile,
  the same float32 accumulation, one rounding), and a plain per-group
  product's;
- gate, up and the activation in ONE call against the two products and the
  activation apart, bit for bit;
- the SwiGLU against PR 48's three ``gmm`` calls row for row, and against
  plain per-expert dots;
- the aligned layout: one sort, every group on a multiple of 16, the
  padding behind its own group;
- a layer's share against the dense per-token reference at the three
  families' (experts, picks) and a partition of the experts adding up;
- the times one product passes a matrix through the MXU, by the pure
  function and by the counter the engine's programs return;
- the warm start's shape: a walked stack lowers ONE set of kernel bodies
  whatever its depth, and traces the share once a process.
"""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas.ops.tpu.megablox import gmm

from ray_tpu.ops import grouped_matmul as grouped
from ray_tpu.parallel import expert

D, F = 128, 256

# name -> (rows of each expert); m / E of 4, 8, 16, 32 and 300 and the
# shapes a copy or a chunk can trip on (a call's chunk: 128 rows, or the
# power of two that covers a smaller call)
SIZES = {
    "mean4_empty_experts": [0, 9, 0, 0, 7, 16, 0, 0],              # chunk 32
    "mean8_on_copy_edges": [16, 0, 16, 32, 0, 0, 0, 0],            # chunk 64
    "mean16_one_above_the_chunk": [200, 3, 1, 52],                 # 128: two
    "mean32_all_on_one_expert": [0, 0, 128, 0],                    # 128: one
    "mean32_ragged": [33, 64, 0, 31],                              # 128
    "mean300": [290, 310],                                         # 128: 3 + 3
    "one_row": [0, 1, 0, 0, 0, 0, 0, 0],                           # chunk 16
    # around a copy's and a chunk's edges: 1, 15, 16, 17, 128, 129 rows,
    # and 445 = three chunks and 61 rows (copies of 32, 16 and 16)
    "edges_1_15_16_17": [1, 0, 15, 16, 17, 0],                     # chunk 64
    "edges_128_129": [128, 0, 129],                                # 128
    "edges_445": [0, 445, 2],                                      # 128
}
# SwiGLU against the parent's: its activation rounds sigmoid, silu and the
# product to bf16 one after the other on the CPU, ours once (float32 inside
# the ``up`` call's epilogue): h differs by at most 2 ulp of bf16 (2 ** -7
# relative), and a row of the down product sums F of them
SWIGLU_ATOL = 0.03


def _weights(e, dtype=jnp.bfloat16):
    ks = jax.random.split(jax.random.PRNGKey(e), 3)
    return {"w_gate": (jax.random.normal(ks[0], (e, D, F)) / D ** .5
                       ).astype(dtype),
            "w_up": (jax.random.normal(ks[1], (e, D, F)) / D ** .5
                     ).astype(dtype),
            "w_down": (jax.random.normal(ks[2], (e, F, D)) / F ** .5
                       ).astype(dtype)}


def _rows(m, dtype=jnp.bfloat16):
    return jax.random.normal(jax.random.PRNGKey(m), (m, D)).astype(dtype)


def _plain_swiglu(x, w, g):
    """One row through expert g's three matrices, in float64 numpy."""
    x = np.asarray(x, np.float64)
    gate = x @ np.asarray(w["w_gate"][g], np.float64)
    up = x @ np.asarray(w["w_up"][g], np.float64)
    return (gate / (1 + np.exp(-gate)) * up) @ np.asarray(w["w_down"][g],
                                                          np.float64)


def _dense_reference(g, idx, wts, w):
    """Every token through each of its picks, in float64."""
    return np.stack([
        sum(float(we) * _plain_swiglu(g[i], w, j)
            for j, we in zip(np.asarray(idx[i]), np.asarray(wts[i])))
        for i in range(g.shape[0])])


def _aligned(sizes):
    """The aligned layout of rows already sorted by group, checked row by
    row: (take, lie) of grouped.aligned_order."""
    m, e = sum(sizes), len(sizes)
    key = jnp.asarray(np.repeat(np.arange(e), sizes), jnp.int32)
    take, lie = grouped.aligned_order(key, jnp.asarray(sizes, jnp.int32))
    assert take.shape[0] == grouped.aligned_rows(m, e)
    starts = np.cumsum([0] + [-(-s // 16) * 16 for s in sizes])[:-1]
    want = np.concatenate([starts[g] + np.arange(s)
                           for g, s in enumerate(sizes)])
    np.testing.assert_array_equal(np.asarray(lie), want)
    np.testing.assert_array_equal(np.asarray(take)[want], np.arange(m))
    return take, lie


def _parent_grouped_swiglu(xs, w_gate, w_up, w_down, sizes):
    """``grouped_swiglu`` as PR 48 left it: dense sorted rows padded to
    tiles of 128, three stock ``gmm`` calls, the activation between."""
    m = xs.shape[0]
    tm = min(128, -(-m // 16) * 16)
    xs = jnp.pad(xs, ((0, -m % tm), (0, 0)))

    def product(a, w, dtype):
        return gmm(a, w, sizes, preferred_element_type=dtype,
                   tiling=(tm, w.shape[1], w.shape[2]), interpret=True)

    gate = product(xs, w_gate, xs.dtype)
    up = product(xs, w_up, xs.dtype)
    return product(jax.nn.silu(gate) * up, w_down, jnp.float32)[:m]


@pytest.mark.parametrize("name", sorted(SIZES))
def test_one_product_is_the_stock_gmm_bit_for_bit(name):
    sizes = jnp.asarray(SIZES[name], jnp.int32)
    m, e = int(sizes.sum()), len(SIZES[name])
    xs, w = _rows(m), _weights(e)["w_gate"]
    take, lie = _aligned(SIZES[name])
    walk, n = grouped.walk_of(sizes)
    assert int(n) == sum(s > 0 for s in SIZES[name]) <= walk.shape[1]
    got = grouped.grouped_matmul(
        xs[jnp.minimum(take, m - 1)], w, walk, n,
        c=grouped.chunk_rows(take.shape[0]), tn=F // 2,
        out_dtype=jnp.float32, interpret=True)[lie]
    tm = -(-m // 16) * 16
    want = gmm(jnp.pad(xs, ((0, tm - m), (0, 0))), w, sizes,
               preferred_element_type=jnp.float32, tiling=(tm, D, F),
               interpret=True)[:m]
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("name", sorted(SIZES))
def test_swiglu_is_the_parents_row_for_row_and_the_plain_dots(name):
    sizes = jnp.asarray(SIZES[name], jnp.int32)
    m, e = int(sizes.sum()), len(SIZES[name])
    xs, w = _rows(m), _weights(e)
    take, lie = _aligned(SIZES[name])
    got = expert.grouped_swiglu(xs[jnp.minimum(take, m - 1)], w["w_gate"],
                                w["w_up"], w["w_down"], sizes)[lie]
    parent = _parent_grouped_swiglu(xs, w["w_gate"], w["w_up"], w["w_down"],
                                    sizes)
    np.testing.assert_allclose(np.asarray(got), np.asarray(parent),
                               atol=SWIGLU_ATOL)
    plain = np.stack([_plain_swiglu(xs[j], w, g) for j, g in enumerate(
        np.repeat(np.arange(e), SIZES[name]))])
    # bf16 intermediates (gate, up, h) against float64: three roundings of
    # 2 ** -9 relative on values of order 1, summed over F
    np.testing.assert_allclose(np.asarray(got), plain, atol=0.06)


@pytest.mark.parametrize("parts", [(8,), (3, 5), (1, 1, 6), (2, 2, 2, 2)],
                         ids=lambda p: "held_" + "_".join(map(str, p)))
def test_shares_of_a_partition_add_up(parts):
    """``held`` a proper sub-range: picks of experts held elsewhere sort
    last, are never visited and stay out of the sum."""
    e, n, k = 8, 40, 3
    w = _weights(e, jnp.float32)
    g = _rows(n, jnp.float32)
    scores = jax.random.normal(jax.random.PRNGKey(9), (n, e))
    wts, idx = jax.lax.top_k(jax.nn.softmax(scores), k)
    idx = idx.astype(jnp.int32)
    whole = expert.expert_share(g, idx, wts, w, range(e))
    shares, start = [], 0
    for size in parts:
        held = range(start, start + size)
        shares.append(expert.expert_share(
            g, idx, wts, {name: m[held.start:held.stop]
                          for name, m in w.items()}, held))
        start += size
    np.testing.assert_allclose(np.asarray(sum(shares)), np.asarray(whole),
                               atol=2e-5)
    np.testing.assert_allclose(np.asarray(whole),
                               _dense_reference(g, idx, wts, w), atol=2e-5)


@pytest.mark.parametrize("m,e,a,c", [
    (4096, 128, 6016, 128), (2048, 128, 3968, 128), (256, 32, 736, 128),
    (1024, 256, 4864, 128), (8, 128, 128, 128), (74, 2, 112, 64),
    (4, 4, 64, 64), (1, 2, 16, 16)])
def test_the_layout_and_the_chunk_follow_from_rows_and_experts(m, e, a, c):
    assert grouped.aligned_rows(m, e) == a
    assert grouped.chunk_rows(a) == c


@pytest.mark.parametrize("m", [4096, 2048], ids=["pass_of_2B", "pass_of_B"])
def test_visits_are_touched_plus_groups_above_the_chunk(m):
    """SDAR's two passes (128 experts, top-8): skewed picks, no group of
    more than two chunks."""
    rng = np.random.default_rng(m)
    e, t = 128, 128
    logits = rng.gumbel(size=(m // 8, e)) + 1.5 * rng.normal(size=(e,))
    logits[:, :8] = -np.inf                          # eight experts untouched
    idx = np.argsort(-logits, axis=1)[:, :8]
    sizes = np.minimum(np.bincount(idx.reshape(-1), minlength=e), 2 * t)
    touched, above = int((sizes > 0).sum()), int((sizes > t).sum())
    assert 100 < touched <= 120 and above > 0
    assert int(expert.product_visits(sizes, m)) == touched + above
    assert int(grouped.walk_of(jnp.asarray(sizes, jnp.int32))[1]) == touched


def test_the_programs_counter_is_the_pure_functions():
    """engine._experts_touched on a routing record: [touched by live rows,
    visits over every row of the call], summed over the routed layers."""
    from ray_tpu.serve.llm.engine import LLMEngine
    e, k, w, rows_a_slot, layers = 16, 2, 6, 8, 3
    rng = np.random.default_rng(3)
    chosen = np.stack([
        np.argsort(-(rng.gumbel(size=(w * rows_a_slot, e))
                     + 2.0 * rng.normal(size=(e,))), axis=1)[:, :k]
        for _ in range(layers)]).astype(np.int32)
    eng = types.SimpleNamespace(
        _jax=jax, _jnp=jnp, cfg=types.SimpleNamespace(max_batch_size=64),
        _cache_spec=types.SimpleNamespace(routed_layers=layers, n_experts=e,
                                          top_k=k))
    idx = jnp.asarray([0, 5, 9, 64, 3, 64], jnp.int32)      # two trash lanes
    got = np.asarray(LLMEngine._experts_touched(
        eng, {"routing": jnp.asarray(chosen)}, idx, rows_a_slot))
    live = np.repeat(np.asarray(idx) != 64, rows_a_slot)
    m = w * rows_a_slot * k
    sizes = np.stack([np.bincount(c.reshape(-1), minlength=e)
                      for c in chosen])
    assert got[0] == sum(len(np.unique(c[live])) for c in chosen)
    assert got[1] == sum(int((-(-s // 128)).sum())
                         for s in sizes) == int(
                             expert.product_visits(sizes, m))
    assert got[1] >= sum(int((s > 0).sum()) for s in sizes)


@pytest.mark.parametrize("sizes,rows,fits", [
    ([3, 5, 4, 4], 16, False),      # 16 dense rows: the layout takes 64
    ([3, 5, 4, 4], 64, True),
    ([0, 16, 0, 16], 32, True),     # on the copies' edges: nothing wasted
    ([0, 16, 0, 17], 32, False),
    ([0, 0, 0, 0], 16, True),       # no step: nothing to overrun
], ids=["dense", "aligned", "on_edges", "one_row_over", "no_rows"])
def test_a_walk_past_the_rows_walks_nothing(sizes, rows, fits):
    """The kernel's copies are unchecked: rows handed over densely packed
    (the megablox layout) with sizes whose aligned layout is longer must
    not be read or written past their end. Such a call visits no group
    (interpreted: unvisited rows read NaN), and a call that fits is
    visited as ever."""
    e = len(sizes)
    w = _weights(e)
    walk, n = grouped.walk_of(jnp.asarray(sizes, jnp.int32))
    assert bool(grouped.walk_fits(walk, n, rows)) == fits
    xs = _rows(rows)
    ys = np.asarray(expert.grouped_swiglu(
        xs, w["w_gate"], w["w_up"], w["w_down"],
        jnp.asarray(sizes, jnp.int32)))
    starts = np.cumsum([0] + [-(-s // 16) * 16 for s in sizes])[:-1]
    if not fits:
        assert np.isnan(ys).all()
        return
    for g, (at, s) in enumerate(zip(starts, sizes)):
        np.testing.assert_allclose(
            ys[at:at + s], _plain_swiglu(xs[at:at + s], w, g),
            atol=SWIGLU_ATOL)


@pytest.mark.parametrize("k,n,tn", [
    (2048, 768, 768), (768, 2048, 2048),        # SDAR, JoyAI
    (2048, 1792, 896), (1792, 2048, 1024),      # LFM2
    (128, 256, 256), (16384, 256, 128), (4096, 1536, 384)])
def test_a_products_columns_are_whole_lane_tiles_under_the_budget(k, n, tn):
    assert expert._columns(k, n) == tn
    assert k * tn <= 2048 * 1024 and n % tn == 0


@pytest.mark.parametrize("k,n", [(8192, 768), (32768, 128), (4096, 1000)])
def test_a_width_with_no_column_tile_is_refused(k, n):
    """All of k stays in one tile: a width whose halves stop being whole
    128s above the budget is refused, not run with a tile of 192 columns
    or over the VMEM."""
    with pytest.raises(ValueError, match="does not tile k"):
        expert._columns(k, n)


def _walked(sizes):
    """(rows in the aligned layout, walk, n, lie, chunk) of a size
    pattern whose rows are sorted by group."""
    m = sum(sizes)
    take, lie = _aligned(sizes)
    walk, n = grouped.walk_of(jnp.asarray(sizes, jnp.int32))
    return (_rows(m)[jnp.minimum(take, m - 1)], walk, n, lie,
            grouped.chunk_rows(take.shape[0]))


@pytest.mark.parametrize("tn", [F, F // 2], ids=["all_columns", "tn_half"])
@pytest.mark.parametrize("name", sorted(SIZES))
def test_one_product_is_the_plain_per_group_product(name, tn):
    sizes = SIZES[name]
    xs, walk, n, lie, c = _walked(sizes)
    w = _weights(len(sizes))["w_up"]
    got = grouped.grouped_matmul(xs, w, walk, n, c=c, tn=tn,
                                 out_dtype=jnp.float32, interpret=True)[lie]
    owner = np.repeat(np.arange(len(sizes)), sizes)
    want = np.stack([np.asarray(x, np.float64) @ np.asarray(w[g], np.float64)
                     for x, g in zip(xs[lie], owner)])
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-5)


@pytest.mark.parametrize("tn", [F, F // 2], ids=["all_columns", "tn_half"])
@pytest.mark.parametrize("name", sorted(SIZES))
def test_gate_and_up_in_one_call_are_the_two_products_apart(name, tn):
    """``rhs_up`` given: both matrices in a grid step, each product
    rounded as a call of its own rounds it, the activation in float32,
    rounded once: bit for bit what two calls and the ops between give."""
    sizes = SIZES[name]
    xs, walk, n, lie, c = _walked(sizes)
    w = _weights(len(sizes))
    how = dict(c=c, tn=tn, out_dtype=jnp.bfloat16, interpret=True)
    got = grouped.grouped_matmul(xs, w["w_gate"], walk, n,
                                 rhs_up=w["w_up"], **how)[lie]
    gate = grouped.grouped_matmul(xs, w["w_gate"], walk, n, **how)[lie]
    up = grouped.grouped_matmul(xs, w["w_up"], walk, n, **how)[lie]
    want = (jax.nn.silu(gate.astype(jnp.float32))
            * up.astype(jnp.float32)).astype(jnp.bfloat16)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


@pytest.mark.parametrize("seed,m,e,elsewhere", [
    (0, 64, 8, 0.0), (1, 200, 4, 0.3), (2, 37, 16, 0.5), (3, 5, 32, 0.0),
    (4, 96, 3, 1.0)], ids=["all_held", "some_elsewhere", "half_elsewhere",
                            "fewer_rows_than_groups", "none_held"])
def test_the_aligned_order_is_one_sort_with_its_padding(seed, m, e,
                                                        elsewhere):
    """Rows in any order, some of no group (experts held elsewhere: key
    e): ``take`` and ``lie`` undo each other on the rows of a group, every
    group starts on a multiple of 16 with its rows in their own order,
    the padding lies behind its own group and rows of no group behind
    every group."""
    rng = np.random.default_rng(seed)
    key = rng.integers(0, e, size=m)
    key[rng.random(m) < elsewhere] = e
    sizes = np.bincount(key, minlength=e + 1)[:e]
    take, lie = map(np.asarray, grouped.aligned_order(
        jnp.asarray(key, jnp.int32), jnp.asarray(sizes, jnp.int32)))
    a = grouped.aligned_rows(m, e)
    assert take.shape == (a,) and lie.shape == (m,)
    assert sorted(take) == list(range(a))            # one permutation
    np.testing.assert_array_equal(take[lie], np.arange(m))
    starts = np.cumsum([0] + [-(-s // 16) * 16 for s in sizes])
    assert (starts % 16 == 0).all() and starts[-1] <= a
    for g in range(e):
        rows = take[starts[g]:starts[g] + sizes[g]]
        np.testing.assert_array_equal(rows, np.flatnonzero(key == g))
        assert (take[starts[g] + sizes[g]:starts[g + 1]] >= m).all()
    held = key < e
    assert (lie[held] < starts[-1]).all() and (lie[~held] >= starts[-1]).all()


@pytest.mark.parametrize("e,k,d,f,n", [
    (32, 4, 256, 224, 24), (128, 8, 256, 96, 12), (256, 8, 256, 96, 9)],
    ids=["lfm2", "sdar", "joyai"])
def test_a_layers_share_is_the_dense_per_token_reference(e, k, d, f, n):
    """The three families' experts and picks a token, their widths cut by
    eight (LFM2's 1,792 leaves 224: no whole lane tile, one column step),
    a few tokens: skewed scores, so some experts get several rows and
    most none."""
    ks = jax.random.split(jax.random.PRNGKey(e + n), 5)
    w = {"w_gate": jax.random.normal(ks[0], (e, d, f)) / d ** .5,
         "w_up": jax.random.normal(ks[1], (e, d, f)) / d ** .5,
         "w_down": jax.random.normal(ks[2], (e, f, d)) / f ** .5}
    g = jax.random.normal(ks[3], (n, d))
    scores = jax.random.normal(ks[4], (n, e)) \
        + 2.0 * jax.random.normal(ks[4], (e,))
    wts, idx = jax.lax.top_k(jax.nn.softmax(scores), k)
    got = expert.expert_share(g, idx.astype(jnp.int32), wts, w, range(e))
    np.testing.assert_allclose(np.asarray(got),
                               _dense_reference(g, idx, wts, w), atol=2e-5)


def _stack_text(layers: int, rows: int = 8, e: int = 8, k: int = 2):
    """The text a walked stack of routed layers lowers to for the TPU
    (Mosaic kernels, no chip needed to lower), and the stack's shapes."""
    shapes = dict(
        g=jax.ShapeDtypeStruct((rows, D), jnp.bfloat16),
        idx=jax.ShapeDtypeStruct((layers, rows, k), jnp.int32),
        w=jax.ShapeDtypeStruct((rows, k), jnp.float32),
        ws=[{n: jax.ShapeDtypeStruct((e, *s), jnp.bfloat16)
             for n, s in (("w_gate", (D, F)), ("w_up", (D, F)),
                          ("w_down", (F, D)))} for _ in range(layers)])

    def stack(g, idx, w, ws):
        for i, ex in enumerate(ws):
            y = expert._share(g, idx[i], w, ex["w_gate"], ex["w_up"],
                              ex["w_down"], held=range(e), interpret=False)
            g = g + y.astype(g.dtype)
        return g

    return jax.jit(stack).trace(**shapes).lower(
        lowering_platforms=("tpu",)).as_text()


def test_a_walked_stack_lowers_one_set_of_kernels_and_traces_once(
        monkeypatch):
    """What a warm start pays a program: the share is a jitted function
    with the layer's weights as operands, so a stack of 2 and one of 6
    routed layers hold the same kernel bodies (gate-and-up, down: one set
    a row count, not one a layer) and the second trace of the same shapes
    runs the share's Python not at all (its body is counted here: inside
    another trace ``_cache_size`` stays 0, that cache is the dispatch
    path's)."""
    traced = []
    real = grouped.aligned_order
    monkeypatch.setattr(grouped, "aligned_order",
                        lambda *a: traced.append(1) or real(*a))
    expert._share.clear_cache()
    two = _stack_text(2)
    six = _stack_text(6)
    assert len(traced) == 1
    assert two.count("tpu_custom_call") == six.count("tpu_custom_call") == 2
    assert six.count("call @_share") == 6 and two.count("call @_share") == 2
