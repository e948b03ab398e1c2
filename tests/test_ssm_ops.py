"""ops/ssm.py against the token-by-token recurrence it states: the chunked
scan from a NON-zero carried state (chunks, a partial last chunk, padding
columns with dt = 0), and the in-place decode update (the Pallas kernel in
interpret mode and its ``jax.numpy`` form) on a pool of rows: neighbouring
rows, the trash row shared by idle lanes, every other row untouched."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.ops import ssm

H, P, G, N = 4, 16, 2, 16


def _inputs(seed, t):
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    x = jax.random.normal(ks[0], (t, H, P), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (t, H)) - 2.0)
    a = -jnp.exp(jax.random.uniform(ks[2], (H,), minval=0.0, maxval=2.5))
    b = jax.random.normal(ks[3], (t, G, N), jnp.float32)
    c = jax.random.normal(ks[4], (t, G, N), jnp.float32)
    s0 = jax.random.normal(ks[5], (H, N, P), jnp.float32)
    return x, dt, a, b, c, s0


def _recurrence(x, dt, a, b, c, s):
    """The module docstring's two lines, a token at a time, in numpy."""
    x, dt, a, b, c = (np.asarray(v, np.float64) for v in (x, dt, a, b, c))
    s = np.asarray(s, np.float64).copy()
    ys = []
    for t in range(x.shape[0]):
        y = np.zeros((H, P))
        for h in range(H):
            g = h // (H // G)
            s[h] = np.exp(dt[t, h] * a[h]) * s[h] \
                + dt[t, h] * np.outer(b[t, g], x[t, h])
            y[h] = c[t, g] @ s[h]
        ys.append(y)
    return np.stack(ys), s


@pytest.mark.parametrize("t,chunk", [(8, 8), (24, 8), (21, 8), (5, 8),
                                     (32, 16)])
def test_chunk_scan_is_the_recurrence_from_a_carried_state(t, chunk):
    x, dt, a, b, c, s0 = _inputs(t, t)
    y, s = ssm.chunk_scan(x, dt, a, b, c, s0, chunk)
    want_y, want_s = _recurrence(x, dt, a, b, c, s0)
    np.testing.assert_allclose(y, want_y, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(s, want_s, rtol=2e-4, atol=2e-4)
    # not the answer from zeros: the carried state is read
    y0, _ = ssm.chunk_scan(x, dt, a, b, c, jnp.zeros_like(s0), chunk)
    assert float(jnp.max(jnp.abs(y0 - y))) > 0.1


def test_chunk_scan_in_two_calls_is_one_call():
    x, dt, a, b, c, s0 = _inputs(3, 40)
    y, s = ssm.chunk_scan(x, dt, a, b, c, s0, 8)
    y1, s1 = ssm.chunk_scan(x[:19], dt[:19], a, b[:19], c[:19], s0, 8)
    y2, s2 = ssm.chunk_scan(x[19:], dt[19:], a, b[19:], c[19:], s1, 8)
    np.testing.assert_allclose(jnp.concatenate([y1, y2]), y, rtol=2e-4,
                               atol=2e-4)
    np.testing.assert_allclose(s2, s, rtol=2e-4, atol=2e-4)


def test_a_column_with_dt_zero_leaves_the_state():
    x, dt, a, b, c, s0 = _inputs(5, 16)
    real = 11
    masked = dt.at[real:].set(0.0)
    _, s = ssm.chunk_scan(x, masked, a, b, c, s0, 8)
    _, want = _recurrence(x[:real], dt[:real], a, b[:real], c[:real], s0)
    np.testing.assert_allclose(s, want, rtol=2e-4, atol=2e-4)


def _pool_case(seed, rows):
    nb = len(rows)
    x, dt, a, b, c, _ = _inputs(seed, nb)
    pool = jax.random.normal(jax.random.PRNGKey(seed + 100),
                             (6, H, N, P), jnp.float32)
    return pool, jnp.asarray(rows, jnp.int32), x, dt, a, b, c


@pytest.mark.parametrize("impl", ["kernel", "xla"])
@pytest.mark.parametrize("rows", [[3, 4], [5, 1, 2], [2, 0, 0, 4]])
def test_decode_update_writes_its_rows_in_place(impl, rows):
    pool, r, x, dt, a, b, c = _pool_case(len(rows), rows)
    fn = (lambda *o: ssm.decode_update(*o, interpret=True)) \
        if impl == "kernel" else ssm.decode_update_xla
    y, out = fn(pool, r, x, dt, a, b, c)
    live = [i for i, row in enumerate(rows) if row != 0]
    for i in live:
        want_y, want_s = _recurrence(x[i:i + 1], dt[i:i + 1], a,
                                     b[i:i + 1], c[i:i + 1], pool[rows[i]])
        np.testing.assert_allclose(y[i], want_y[0], rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(out[rows[i]], want_s, rtol=2e-5,
                                   atol=2e-5)
    untouched = [j for j in range(pool.shape[0])
                 if j not in rows and j != 0]
    np.testing.assert_array_equal(out[jnp.asarray(untouched)],
                                  pool[jnp.asarray(untouched)])
    assert bool(jnp.all(jnp.isfinite(out)))


def test_the_kernel_is_its_plain_form():
    pool, r, x, dt, a, b, c = _pool_case(9, [1, 2, 4, 5])
    y, out = ssm.decode_update(pool, r, x, dt, a, b, c, interpret=True)
    y2, out2 = ssm.decode_update_xla(pool, r, x, dt, a, b, c)
    np.testing.assert_allclose(y, y2, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(out, out2, rtol=1e-6, atol=1e-6)


def test_update_after_scan_is_the_recurrence():
    """A prefill's scan, then decode steps on the row it left."""
    x, dt, a, b, c, s0 = _inputs(11, 20)
    want_y, want_s = _recurrence(x, dt, a, b, c, s0)
    _, s = ssm.chunk_scan(x[:17], dt[:17], a, b[:17], c[:17], s0, 8)
    pool = jnp.zeros((3, H, N, P), jnp.float32).at[2].set(s)
    for t in range(17, 20):
        y, pool = ssm.decode_update(
            pool, jnp.asarray([2], jnp.int32), x[t][None], dt[t][None], a,
            b[t][None], c[t][None], interpret=True)
        np.testing.assert_allclose(y[0], want_y[t], rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(pool[2], want_s, rtol=2e-4, atol=2e-4)
