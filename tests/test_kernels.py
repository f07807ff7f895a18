"""Per-kernel shape/dtype sweeps: Pallas (interpret mode) vs jnp oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.flash_attention import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.gla_chunk.gla_chunk import gla_chunk_kernel
from repro.kernels.gla_chunk.ref import gla_ref
from repro.kernels.hash_join.ops import hash_join
from repro.kernels.hash_join.ref import hash_join_ref
from repro.kernels.segment_kpi.ops import segment_kpi
from repro.kernels.segment_kpi.ref import segment_kpi_ref


@pytest.mark.parametrize("b,hq,hkv,s,d,causal,dtype,tol", [
    (2, 4, 2, 256, 64, True, jnp.float32, 2e-5),
    (1, 8, 8, 384, 128, True, jnp.bfloat16, 2e-2),
    (2, 6, 2, 256, 64, False, jnp.float32, 2e-5),
    (1, 12, 4, 512, 64, True, jnp.bfloat16, 2e-2),
    (1, 2, 1, 128, 128, True, jnp.float32, 2e-5),
])
def test_flash_attention_sweep(b, hq, hkv, s, d, causal, dtype, tol):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, hq, s, d), dtype)
    k = jax.random.normal(ks[1], (b, hkv, s, d), dtype)
    v = jax.random.normal(ks[2], (b, hkv, s, d), dtype)
    out = flash_attention(q, k, v, causal=causal, interpret=True)
    ref = attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("bh,s,dk,dv,inclusive,use_u,chunk", [
    (4, 256, 64, 64, False, True, 64),     # rwkv6 regime
    (2, 128, 64, 128, True, False, 64),    # mamba2/SSD regime
    (3, 192, 32, 32, False, False, 64),
    (1, 512, 128, 64, True, False, 128),
])
def test_gla_chunk_sweep(bh, s, dk, dv, inclusive, use_u, chunk):
    ks = jax.random.split(jax.random.PRNGKey(1), 5)
    q = jax.random.normal(ks[0], (bh, s, dk), jnp.float32)
    k = jax.random.normal(ks[1], (bh, s, dk), jnp.float32)
    v = jax.random.normal(ks[2], (bh, s, dv), jnp.float32)
    lw = -jnp.exp(jax.random.normal(ks[3], (bh, s, dk)))
    u = jax.random.normal(ks[4], (bh, dk), jnp.float32) if use_u else None
    out = gla_chunk_kernel(q, k, v, lw, u, inclusive=inclusive, chunk=chunk,
                           interpret=True)
    ref = gla_ref(q, k, v, lw, u, inclusive=inclusive, chunk=chunk)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_gla_chunk_matches_step_recurrence():
    """Chunked kernel vs the token-by-token recurrence (decode path)."""
    from repro.models.gla import gla_step
    bh, s, dk, dv = 2, 128, 32, 32
    ks = jax.random.split(jax.random.PRNGKey(2), 4)
    q = jax.random.normal(ks[0], (bh, s, dk), jnp.float32)
    k = jax.random.normal(ks[1], (bh, s, dk), jnp.float32)
    v = jax.random.normal(ks[2], (bh, s, dv), jnp.float32)
    lw = -jnp.exp(jax.random.normal(ks[3], (bh, s, dk)))
    out = gla_chunk_kernel(q, k, v, lw, None, inclusive=True, chunk=64,
                           interpret=True)
    S = jnp.zeros((bh, 1, dk, dv))
    outs = []
    for t in range(s):
        o, S = gla_step(q[:, t, None], k[:, t, None], v[:, t, None],
                        lw[:, t, None], S, inclusive=True)
        outs.append(o[:, 0])
    ref = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=3e-4, atol=3e-4)


@pytest.mark.parametrize("n_slots,n_keys,n_queries", [
    (512, 300, 128), (1024, 700, 512), (256, 50, 64)])
def test_hash_join_sweep(n_slots, n_keys, n_queries):
    from repro.core.cache import InMemoryTable
    rng = np.random.default_rng(0)
    tbl = InMemoryTable(n_slots)
    keys = rng.choice(10**6, n_keys, replace=False).astype(np.int64)
    tbl.upsert(keys, rng.normal(size=(n_keys, 8)).astype(np.float32),
               np.arange(n_keys, dtype=np.int64))
    queries = jnp.asarray(np.concatenate(
        [rng.choice(keys, n_queries // 2),
         rng.integers(2 * 10**6, 3 * 10**6, n_queries - n_queries // 2)]),
        jnp.int32)
    kt, vt, tt = tbl.device_state()
    got = hash_join(queries, kt, vt, tt)
    _assert_bitwise(got, hash_join_ref(queries, kt, vt, tt))
    assert float(jnp.asarray(got[1], jnp.float32).mean()) >= 0.49


def _assert_bitwise(got, want):
    for g, w in zip(got, want):
        g, w = np.asarray(g), np.asarray(w)
        assert g.dtype == w.dtype and g.shape == w.shape
        assert g.tobytes() == w.tobytes()


def _keys_hashing_to(slot, n_slots, count, rng):
    from repro.core.cache import hash32_np
    cand = rng.integers(0, 2**31 - 1, 400_000)
    return cand[hash32_np(cand) % n_slots == slot][:count]


@pytest.mark.parametrize("n_slots,n_keys", [
    (1024, 250), (1280, 150), (4096, 1000), (1536, 300)])
def test_hash_join_slot_tiles(n_slots, n_keys):
    """Tables spanning several slot tiles, with probe chains planted
    across a tile edge and across the wrap from the last slot to slot 0:
    the tiled probe is bit-identical to the sequential oracle."""
    from repro.core.cache import InMemoryTable
    from repro.kernels.hash_join.hash_join import SLOT_TILE
    rng = np.random.default_rng(n_slots)
    edge = _keys_hashing_to(SLOT_TILE - 2, n_slots, 6, rng)
    wrap = _keys_hashing_to(n_slots - 2, n_slots, 6, rng)
    other = rng.choice(10**6, n_keys, replace=False) + 2**30
    tbl = InMemoryTable(n_slots)
    for keys in (np.concatenate([edge, wrap]), other):   # chains first
        tbl.upsert(keys, rng.normal(size=(len(keys), 8)).astype(np.float32),
                   rng.integers(0, 10**6, len(keys)))
    assert tbl.n_slots == n_slots               # no rehash: chains as planted
    slot_of = {int(k): int(np.nonzero(tbl.keys == k)[0][0])
               for k in np.concatenate([edge, wrap])}
    assert max(slot_of[int(k)] for k in edge) >= SLOT_TILE    # crosses edge
    assert min(slot_of[int(k)] for k in wrap) < 8             # wraps to 0
    absent = rng.integers(2 * 10**9, 2**31 - 1, 400)
    queries = jnp.asarray(np.concatenate(
        [edge, wrap, rng.choice(other, 400), absent,
         _keys_hashing_to(n_slots - 1, n_slots, 4, rng)])[:768], jnp.int32)
    kt, vt, tt = tbl.device_state()
    got = hash_join(queries, kt, vt, tt)
    _assert_bitwise(got, hash_join_ref(queries, kt, vt, tt))
    assert bool(np.asarray(got[1])[:len(edge) + len(wrap)].all())


def test_segment_kpi_sweep():
    rng = np.random.default_rng(3)
    for n, units in [(256, 8), (1000, 20), (513, 32)]:
        prod = np.abs(rng.normal(size=(n, 8))).astype(np.float32)
        prod[:, 1] = rng.integers(0, units, n)
        prod[:, 4] = prod[:, 3] + np.abs(prod[:, 4]) + 0.1
        eq = np.abs(rng.normal(size=(n, 8))).astype(np.float32)
        eq[:, 1] = prod[:, 1]
        eq[:, 4] = eq[:, 3] + np.abs(eq[:, 4]) + 5
        eq[:, 5] = rng.random(n) > 0.3
        qr = np.abs(rng.normal(size=(n, 8))).astype(np.float32)
        qr[:, 1] = prod[:, 1]
        f_k, a_k = segment_kpi(jnp.asarray(prod), jnp.asarray(eq),
                               jnp.asarray(qr), n_units=units)
        f_r, a_r = segment_kpi_ref(jnp.asarray(prod), jnp.asarray(eq),
                                   jnp.asarray(qr), n_units=units)
        np.testing.assert_allclose(np.asarray(f_k), np.asarray(f_r),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(a_k), np.asarray(a_r),
                                   rtol=1e-4, atol=1e-4)


def test_gla_pipeline_vs_models_gla():
    """kernels/gla_chunk ops wrapper == models.gla (the layer actually
    calls the latter on CPU; the contract must be identical)."""
    from repro.kernels.gla_chunk.ops import gla as gla_op
    from repro.models.gla import gla_chunk
    ks = jax.random.split(jax.random.PRNGKey(5), 5)
    b, s, h, dk, dv = 2, 128, 3, 32, 32
    q = jax.random.normal(ks[0], (b, s, h, dk), jnp.float32)
    k = jax.random.normal(ks[1], (b, s, h, dk), jnp.float32)
    v = jax.random.normal(ks[2], (b, s, h, dv), jnp.float32)
    lw = -jnp.exp(jax.random.normal(ks[3], (b, s, h, dk)))
    u = jax.random.normal(ks[4], (h, dk), jnp.float32)
    out1 = gla_op(q, k, v, lw, u, inclusive=False, chunk=64)
    out2, _ = gla_chunk(q, k, v, lw, u=u, inclusive=False, chunk=64,
                        ratio_dtype=jnp.float32)   # kernel computes in f32
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2),
                               rtol=2e-4, atol=2e-4)
    # the production (bf16-ratio) path stays within ~1% of f32 at tensor
    # scale (individual near-zero elements are not rtol-comparable)
    out3, _ = gla_chunk(q, k, v, lw, u=u, inclusive=False, chunk=64)
    diff = float(jnp.abs(out3 - out2).max())
    scale = float(jnp.abs(out2).max())
    assert diff < 0.01 * scale, (diff, scale)
