"""The DECODE call and the CHUNK call on pools of K and V per head, walking
body against grid body, alone on the chip at the cells' shapes and at WIDE
tables (a builder's tool since PR 61, no part of the benchmark; no code a
cell runs is touched: the table ``WALKS_LIVE`` is patched in THIS process).

chiprun -- python3 tools/paged_kernel_bench.py decode [shape ...] [c2 c4 c8]
    the decode call: grid + scatter | walk + write (| walk behind a scatter)
chiprun -- python3 tools/paged_kernel_bench.py decode wide4k wide16k wide32k
    the same under tables of 4,096 / 16,384 / 32,768 positions, where the
    walk takes 8 / 2 / 1 KV heads a grid step (``_WALK_KV_BYTES``)
chiprun -- python3 tools/paged_kernel_bench.py chunk
    the chunk call at starts 0 / 512 / 1,024 / 1,536
Lines of JSON on stdout and in chiprun_out/paged_kernel_bench.jsonl. PERF.md
sections 6-7 hold what PR 61 read with it."""
import sys, time, json, os
sys.path.insert(0, ".")
import numpy as np
import jax, jax.numpy as jnp
from ray_tpu.ops import paged_attention as po
from ray_tpu.serve.llm import kv_cache as kvc

PAGE, MP = 128, 16      # MP: a table's pages, unless the shape has "mp"
# (slots, pool rows (KV heads, or pairs of heads of 64), query heads a row, lanes, attention layers,
#  pool pages, scanned, context range)
SHAPES = {
    "peak":   dict(b=32, rows=8, n_rep=4, d=128, layers=16, pages=432, scan=True, ctx=(64, 1000)),
    "peak16": dict(b=16, rows=8, n_rep=4, d=128, layers=16, pages=432, scan=True, ctx=(64, 1000)),
    "chat8":  dict(b=8, rows=8, n_rep=4, d=128, layers=16, pages=432, scan=True, ctx=(64, 1000)),
    "chat4":  dict(b=4, rows=8, n_rep=4, d=128, layers=16, pages=432, scan=True, ctx=(64, 1000)),
    "lfm2":   dict(b=64, rows=4, n_rep=8, d=128, layers=4, pages=1024, scan=False, ctx=(64, 1000)),
    "tiny":   dict(b=3, rows=2, n_rep=2, d=128, layers=2, pages=60, scan=True, ctx=(64, 1000)),
    "falcon": dict(b=96, rows=4, n_rep=5, d=128, layers=6, pages=1153, scan=False, ctx=(300, 540)),
    # Mistral's heads under wider tables, contexts up to three quarters of them
    "wide4k": dict(b=16, rows=8, n_rep=4, d=128, layers=2, pages=513, scan=True, ctx=(300, 3900), mp=32),
    "wide16k": dict(b=16, rows=8, n_rep=4, d=128, layers=2, pages=1537, scan=True, ctx=(300, 12000), mp=128),
    "wide32k": dict(b=8, rows=8, n_rep=4, d=128, layers=2, pages=1537, scan=True, ctx=(300, 24000), mp=256),
    "tinywide": dict(b=3, rows=2, n_rep=2, d=128, layers=2, pages=200, scan=True, ctx=(64, 4000), mp=64),
}
OUT = "chiprun_out/paged_kernel_bench.jsonl"
os.makedirs("chiprun_out", exist_ok=True)


def emit(row):
    line = json.dumps(row)
    print(line, flush=True)
    with open(OUT, "a") as f:
        f.write(line + "\n")


def table_of(walks):
    po.WALKS_LIVE = {**po.WALKS_LIVE, "heads": walks}
    po._gqa_walk_call.clear_cache()


def pools_of(s, key):
    kk, kv = jax.random.split(key)
    shape = (s["layers"], s["rows"], s["pages"], PAGE, s["d"])
    return (jax.random.normal(kk, shape, jnp.bfloat16),
            jax.random.normal(kv, shape, jnp.bfloat16))


def over_layers(s, layer_fn, x, k_pool, v_pool):
    """x the running sum of reads; the pools a carry (scanned) or threaded (walked)."""
    if s["scan"]:
        def body(carry, l):
            return layer_fn(*carry, l), None
        (x, k_pool, v_pool), _ = jax.lax.scan(
            body, (x, k_pool, v_pool), jnp.arange(s["layers"], dtype=jnp.int32))
        return x, k_pool, v_pool
    for l in range(s["layers"]):
        x, k_pool, v_pool = layer_fn(x, k_pool, v_pool, l)
    return x, k_pool, v_pool


def bench(f, pools, *a, n=20):
    out = f(*pools, *a)
    jax.block_until_ready(out)
    t = time.perf_counter()
    for _ in range(n):
        out = f(*out[1:], *a)
    jax.block_until_ready(out)
    return (time.perf_counter() - t) / n, out


def decode_program(s, mode, tables):
    """One step's attention calls: ``grid`` the parent's (scatter, grid body), ``walk`` the change's
    (the rows ride in), ``walk_scatter`` the walking body behind the scatter."""
    def layer(x, k_pool, v_pool, l, q, k_new, v_new, pos, page_idx):
        ql = q + l.astype(q.dtype)
        if mode == "walk":
            read, k_pool, v_pool = po.paged_decode_attention(
                ql, k_pool, v_pool, tables, pos, l, write=(k_new, v_new, page_idx))
        else:
            k_pool, v_pool = kvc._write_token_kv(k_pool, v_pool, l, k_new, v_new, page_idx, pos % PAGE)
            read = po.paged_decode_attention(ql, k_pool, v_pool, tables, pos, l)
        return x + read.astype(jnp.float32), k_pool, v_pool

    def f(k_pool, v_pool, q, k_new, v_new, pos):
        page_idx = jnp.take_along_axis(tables, (pos // PAGE)[:, None], axis=1)[:, 0]
        x = jnp.zeros(q.shape, jnp.float32)
        x, k_pool, v_pool = over_layers(
            s, lambda x, k, v, l: layer(x, k, v, jnp.asarray(l, jnp.int32), q, k_new, v_new, pos, page_idx),
            x, k_pool, v_pool)
        return x, k_pool, v_pool
    return jax.jit(f, donate_argnums=(0, 1))


def run_decode(names, variants):
    rng = np.random.default_rng(61)
    for name in names:
        s = SHAPES[name]
        b, rows, h = s["b"], s["rows"], s["rows"] * s["n_rep"]
        key = jax.random.PRNGKey(0)
        pos = jnp.asarray(rng.integers(*s["ctx"], size=b), jnp.int32)
        # a slot's table: its live pages, distinct pages of the pool, then the trash page
        free, tables = iter(1 + rng.permutation(s["pages"] - 1)), np.zeros((b, s.get("mp", MP)), np.int32)
        for slot, n in enumerate(np.asarray(pos) // PAGE + 1):
            tables[slot, :n] = [next(free) for _ in range(n)]
        tables = jnp.asarray(tables)
        q = jax.random.normal(key, (b, h, s["d"]), jnp.bfloat16)
        k_new = jax.random.normal(jax.random.PRNGKey(1), (b, rows, s["d"]), jnp.bfloat16)
        v_new = jax.random.normal(jax.random.PRNGKey(2), (b, rows, s["d"]), jnp.bfloat16)
        live_pages = float(np.ceil((np.asarray(pos) + 1) / PAGE).mean())
        live_bytes = float((np.asarray(pos) + 1).sum()) * rows * s["d"] * 2 * 2
        row = {"call": "decode", "shape": name, "slots": b, "table": s.get("mp", MP) * PAGE, "live_pages": live_pages,
               "roofline_ms": live_bytes / 819e9 * 1e3}
        table_of(("block",))
        sec, want = bench(decode_program(s, "grid", tables), pools_of(s, key), q, k_new, v_new, pos)
        row["grid_scatter_ms_call"] = sec / s["layers"] * 1e3
        for var in variants:
            po._GQA_CHUNK_PAGES = int(var[1:])
            table_of(("decode", "verify", "block"))
            for mode in ("walk", "walk_scatter") if var == variants[0] else ("walk",):
                try:
                    sec, got = bench(decode_program(s, mode, tables), pools_of(s, key), q, k_new, v_new, pos)
                except Exception as e:      # what the compiler refuses is a finding
                    row[f"{mode}_{var}_error"] = repr(e)[:300]
                    continue
                row[f"{mode}_{var}_ms_call"] = sec / s["layers"] * 1e3
                row[f"{mode}_{var}_diff"] = float(jnp.abs(got[0] - want[0]).max())
                if mode == "walk":      # the pools: the scatter's outside the trash page
                    row[f"{mode}_{var}_pools_equal"] = bool(
                        all(bool((g[:, :, 1:] == w[:, :, 1:]).all()) for g, w in zip(got[1:], want[1:])))
        po._GQA_CHUNK_PAGES = 4
        emit(row)


def chunk_program(s, mode, table, c):
    def layer(x, k_pool, v_pool, l, q, k_new, v_new, start, page_idx):
        ql = q + l.astype(q.dtype)
        if mode == "walk":
            read, k_pool, v_pool = po.paged_chunk_attention(
                ql, k_pool, v_pool, table, start, start + c, l, write=(k_new, v_new, page_idx))
        else:
            k_pool, v_pool = kvc._write_token_kv(
                k_pool, v_pool, l, k_new, v_new, page_idx, (start + jnp.arange(c)) % PAGE)
            read = po.paged_chunk_attention(ql, k_pool, v_pool, table, start, start + c, l)
        return x + read.astype(jnp.float32), k_pool, v_pool

    def f(k_pool, v_pool, q, k_new, v_new, start):
        page_idx = table[(start + jnp.arange(c)) // PAGE]
        x = jnp.zeros(q.shape, jnp.float32)
        for l in range(s["layers"]):
            x, k_pool, v_pool = layer(x, k_pool, v_pool, jnp.int32(l), q, k_new, v_new, start, page_idx)
        return x, k_pool, v_pool
    return jax.jit(f, donate_argnums=(0, 1))


def run_chunk(cases):
    rng = np.random.default_rng(61)
    c = 512
    for name, starts in cases:
        s = dict(SHAPES[name], layers=4)
        rows, h = s["rows"], s["rows"] * s["n_rep"]
        key = jax.random.PRNGKey(0)
        table = jnp.asarray(1 + rng.permutation(s["pages"] - 1)[:MP], jnp.int32)
        q = jax.random.normal(key, (1, c, h, s["d"]), jnp.bfloat16)
        k_new = jax.random.normal(jax.random.PRNGKey(1), (c, rows, s["d"]), jnp.bfloat16)
        v_new = jax.random.normal(jax.random.PRNGKey(2), (c, rows, s["d"]), jnp.bfloat16)
        for start in starts:
            row = {"call": "chunk", "shape": name, "rows": c, "start": start}
            st = jnp.int32(start)
            table_of(("block",))
            sec, want = bench(chunk_program(s, "grid", table, c), pools_of(s, key), q, k_new, v_new, st, n=10)
            row["grid_scatter_ms_call"] = sec / s["layers"] * 1e3
            table_of(("decode", "verify", "block", "chunk"))
            try:
                sec, got = bench(chunk_program(s, "walk", table, c), pools_of(s, key), q, k_new, v_new, st, n=10)
                row["walk_write_ms_call"] = sec / s["layers"] * 1e3
                row["diff"] = float(jnp.abs(got[0] - want[0]).max())
                row["ref_max"] = float(jnp.abs(want[0]).max())
            except Exception as e:
                row["walk_error"] = repr(e)[:300]
            emit(row)


if __name__ == "__main__":
    what = sys.argv[1]
    emit({"device": str(jax.devices()[0]), "argv": sys.argv[1:]})
    if what == "decode":
        variants = [a for a in sys.argv[2:] if a[0] == "c" and a[1:].isdigit()] or ["c4"]
        names = [a for a in sys.argv[2:] if a in SHAPES] or [n for n in SHAPES if "tiny" not in n and "wide" not in n]
        run_decode(names, variants)
    else:
        run_chunk([("tiny", (0, 512))] if "tiny" in sys.argv else
                  [("peak", (0, 512, 1024, 1536)), ("lfm2", (0,)), ("falcon", (0,))])
