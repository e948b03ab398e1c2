"""Trace and lower time of the decode CALL alone for a described v5e, by body:
what a program pays at start-up for one kernel or the other (a builder's tool
since PR 61, no part of the benchmark; runs on the CPU, no chip).

python3 tools/paged_call_lowering.py
Prints the call's count of equations too (a walking call with its write was
1,376 before PR 61's ``_div`` / ``_rem``, 425 behind them)."""
import sys, os, time, collections
sys.path.insert(0, os.getcwd())
os.environ.setdefault("TPU_LOG_DIR", "disabled")
import jax, jax.numpy as jnp
jax.config.update("jax_enable_compilation_cache", False)
from ray_tpu.ops import paged_attention as po
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding
topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
one = SingleDeviceSharding(topo.devices[0])
po.interpret_default = lambda: False
B, HKV, NREP, D, L, P, PAGE, MP = 32, 8, 4, 128, 2, 432, 128, 16
def arg(*shape, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one)
args = (arg(B, HKV * NREP, D, dtype=jnp.bfloat16), arg(L, HKV, P, PAGE, D, dtype=jnp.bfloat16), arg(L, HKV, P, PAGE, D, dtype=jnp.bfloat16),
        arg(B, MP), arg(B), arg(), arg(B, HKV, D, dtype=jnp.bfloat16), arg(B, HKV, D, dtype=jnp.bfloat16), arg(B))
def run(label, heads, write):
    po.WALKS_LIVE = {**po.WALKS_LIVE, "heads": heads}
    def f(q, k, v, tables, pos, layer, kn, vn, pidx):
        if write:
            return po.paged_decode_attention(q, k, v, tables, pos, layer, write=(kn, vn, pidx))
        return po.paged_decode_attention(q, k, v, tables, pos, layer)
    out = []
    for _ in range(5):
        po._gqa_walk_call.clear_cache(); jax.clear_caches()
        t0 = time.perf_counter(); traced = jax.jit(f).trace(*args); t1 = time.perf_counter(); traced.lower(); t2 = time.perf_counter()
        out.append((t1 - t0, t2 - t1))
    out.sort(key=sum)
    print(label, "trace %.3f lower %.3f (median of 5)" % out[2], "min total %.3f" % sum(out[0]), flush=True)
for _ in range(2):
    run("grid", ("block",), False)
    run("walk", ("decode", "verify", "block"), False)
    run("walk+write", ("decode", "verify", "block"), True)


def count(jaxpr, c):
    for e in jaxpr.eqns:
        c[e.primitive.name] += 1
        for v in e.params.values():
            for j in (v if isinstance(v, (list, tuple)) else (v,)):
                j = getattr(j, "jaxpr", j)
                if hasattr(j, "eqns"):
                    count(j, c)


for write in (False, True):
    po.WALKS_LIVE = {**po.WALKS_LIVE, "heads": ("decode", "verify", "block")}
    po._gqa_walk_call.clear_cache()

    def f(q, k, v, tables, pos, layer, kn, vn, pidx):
        return po.paged_decode_attention(
            q, k, v, tables, pos, layer,
            **({"write": (kn, vn, pidx)} if write else {}))
    c = collections.Counter()
    count(jax.make_jaxpr(f)(*args).jaxpr, c)
    print("walk" + "+write" * write, "equations", sum(c.values()),
          c.most_common(8))
