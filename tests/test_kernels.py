"""Pallas kernel validation: interpret-mode vs pure-jnp oracles.

Per the deliverable: sweep shapes/dtypes and assert_allclose against the
ref.py oracle for every kernel. The hypothesis property tests on the same
kernels live in tests/test_property.py (skipped when hypothesis is absent,
so this module always runs from a clean checkout).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import pack_bits, plan_tiling, unpack_bits
from repro.kernels import (
    tbn_dense_train,
    tile_construct,
    tile_construct_pallas,
    tiled_dense_infer,
    tiled_matmul_unique,
)
from repro.kernels.ref import (
    tile_construct_ref,
    tiled_matmul_ref,
    tiled_matmul_unique_ref,
    tiled_matvec_unique_ref,
)


def _rand_tile_packed(key, r, k):
    t = jnp.where(jax.random.bernoulli(key, 0.5, (r * k,)), 1.0, -1.0)
    return pack_bits(t).reshape(r, k // 32), t


# --------------------------------------------------------------------------
# tiled_matmul kernel
# --------------------------------------------------------------------------
SHAPES = [
    # (M, K, r) — pre-padded to block multiples (ops.py pads otherwise)
    (8, 32, 8),
    (128, 128, 128),
    (128, 512, 128),
    (256, 256, 64),
    (64, 1024, 256),
]


@pytest.mark.parametrize("m,k,r", SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_tiled_matmul_unique_matches_ref(m, k, r, dtype):
    kx, kt = jax.random.split(jax.random.PRNGKey(m * 7 + k + r))
    x = jax.random.normal(kx, (m, k), dtype)
    packed, t = _rand_tile_packed(kt, r, k)
    bm, br, bk = min(128, m), min(128, r), min(512, k)
    # make blocks divide
    while m % bm:
        bm //= 2
    while r % br:
        br //= 2
    while k % bk or bk % 32:
        bk //= 2
    got = tiled_matmul_unique(
        x, packed, r=r, block_m=bm, block_r=br, block_k=bk, interpret=True
    )
    want = tiled_matmul_unique_ref(x.astype(jnp.float32), packed.reshape(-1), r=r)
    rtol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=rtol, atol=1e-2)


@pytest.mark.parametrize("p", [2, 4, 8])
@pytest.mark.parametrize("alpha_mode", ["layer", "tile"])
def test_tiled_dense_infer_matches_dense_reconstruction(p, alpha_mode):
    n_out, n_in, m = 64 * p, 96, 16
    spec = plan_tiling(
        (n_out, n_in), p=p, min_size=1, alpha_mode=alpha_mode, alpha_source="W"
    )
    key = jax.random.PRNGKey(p)
    kx, kt, ka = jax.random.split(key, 3)
    x = jax.random.normal(kx, (m, n_in))
    t = jnp.where(jax.random.bernoulli(kt, 0.5, (spec.q,)), 1.0, -1.0)
    packed = pack_bits(t)
    alpha = jax.random.uniform(ka, (spec.n_alpha,)) + 0.1
    want = tiled_matmul_ref(x, packed, alpha, n_out=n_out, p=p)
    # pure-JAX structured path (dry-run path)
    got_jnp = tiled_dense_infer(x, packed, alpha, spec, use_pallas=False)
    np.testing.assert_allclose(np.asarray(got_jnp), np.asarray(want), rtol=1e-4, atol=1e-4)
    # pallas interpret path (padding exercised: n_in=96 < block_k)
    got_pl = tiled_dense_infer(x, packed, alpha, spec, use_pallas=True)
    np.testing.assert_allclose(np.asarray(got_pl), np.asarray(want), rtol=1e-4, atol=1e-4)


def test_tiled_dense_infer_batched_leading_dims():
    spec = plan_tiling((128, 64), p=4, min_size=1, alpha_source="W")
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 3, 64))
    t = jnp.where(jax.random.bernoulli(jax.random.PRNGKey(1), 0.5, (spec.q,)), 1.0, -1.0)
    alpha = jnp.abs(jax.random.normal(jax.random.PRNGKey(2), (4,))) + 0.1
    y = tiled_dense_infer(x, pack_bits(t), alpha, spec, use_pallas=True)
    assert y.shape == (2, 3, 128)
    y2 = tiled_dense_infer(x, pack_bits(t), alpha, spec, use_pallas=False)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y2), rtol=1e-4, atol=1e-4)


# --------------------------------------------------------------------------
# decode matvec kernel (small-m fast path)
# --------------------------------------------------------------------------
MATVEC_SHAPES = [
    # (n_in, r) — word-padded rows, non-dividing r/k exercised via ops pads
    (96, 24),
    (512, 128),
    (1504, 300),
]


@pytest.mark.parametrize("m", [1, 3, 8])
@pytest.mark.parametrize("n_in,r", MATVEC_SHAPES)
def test_decode_matvec_matches_ref(m, n_in, r):
    """ops._dense_unique_local routes m <= MATVEC_MAX_M to the decode
    matvec kernel; its result must match the row-packed oracle."""
    from repro.kernels import MATVEC_MAX_M
    from repro.kernels.ops import _dense_unique_local

    assert m <= MATVEC_MAX_M
    kx, kt = jax.random.split(jax.random.PRNGKey(m * 13 + n_in + r))
    x = jax.random.normal(kx, (m, n_in))
    t = jnp.where(jax.random.bernoulli(kt, 0.5, (r, n_in)), 1.0, -1.0)
    packed = pack_bits(t)                       # (r, ceil(n_in/32))
    want = tiled_matvec_unique_ref(x, packed, n_in=n_in)
    got = _dense_unique_local(
        x, packed, n_in=n_in, use_pallas=True,
        block_m=128, block_r=128, block_k=512,
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-3)


@pytest.mark.parametrize("m", [1, 3, 8])
def test_decode_matvec_kernel_direct(m):
    """Direct kernel call at pre-padded shapes (no ops padding)."""
    from repro.kernels import tiled_matvec_unique
    from repro.kernels.tiled_matvec import sublane_rounded

    n_in, r = 256, 64
    kx, kt = jax.random.split(jax.random.PRNGKey(m))
    mp = sublane_rounded(m, jnp.float32)
    x = jax.random.normal(kx, (mp, n_in))
    t = jnp.where(jax.random.bernoulli(kt, 0.5, (r, n_in)), 1.0, -1.0)
    packed = pack_bits(t)
    got = tiled_matvec_unique(x, packed, r=r, block_r=64, block_k=256,
                              interpret=True)
    want = tiled_matvec_unique_ref(x, packed, n_in=n_in)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-3)


# --------------------------------------------------------------------------
# planar unpack (k blocks of whole lane tiles) vs the interleaved unpack
# --------------------------------------------------------------------------
PLANAR_SHAPES = [
    # (n_in, r): one 128-word block with r not a multiple of br; a
    # 448-word row padded to four 128-word blocks (granite's down
    # projection); a 47-word whole-row block, which unpacks interleaved
    (4096, 200),
    (14336, 72),
    (1504, 136),
]


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("n_in,r", PLANAR_SHAPES)
@pytest.mark.parametrize("m", [1, 8, 16, 32, 128, 512])
def test_tile_kernels_match_ref_across_unpack_orders(m, n_in, r, dtype):
    """The serve dispatch at decode m (<= 32: matvec kernel) and extend m
    (matmul kernel) against the row-packed oracle, on both unpack paths.
    The operands are exact in both dtypes, so only the f32 accumulation
    order differs from the oracle's."""
    from repro.kernels.ops import _dense_unique_local
    from repro.kernels.tiled_matmul import is_planar

    words = -(-n_in // 32)
    assert is_planar(min(4096, 32 * words)) == (words >= 128)
    kx, kt = jax.random.split(jax.random.PRNGKey(m * 31 + n_in + r))
    x = jax.random.normal(kx, (m, n_in), dtype)
    t = jnp.where(jax.random.bernoulli(kt, 0.5, (r, n_in)), 1.0, -1.0)
    packed = pack_bits(t)                          # (r, ceil(n_in/32))
    got = _dense_unique_local(
        x, packed, n_in=n_in, use_pallas=True,
        block_m=128, block_r=128, block_k=4096,
    )
    want = tiled_matvec_unique_ref(x.astype(jnp.float32), packed, n_in=n_in)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-3)


@pytest.mark.parametrize("k,block_k", [(4096, 4096), (16384, 4096),
                                       (8192, 8192), (2048, 1024)])
def test_planar_columns_is_the_column_gather(k, block_k):
    """planar_columns puts input column 32j + b of each k block at block
    column b·nw + j; undoing the gather restores x."""
    from repro.kernels.tiled_matmul import is_planar, planar_columns

    nw = block_k // 32
    assert is_planar(block_k) == (nw % 128 == 0)
    x = jax.random.normal(jax.random.PRNGKey(k + block_k), (3, k))
    src = np.array([blk * block_k + 32 * j + b
                    for blk in range(k // block_k)
                    for b in range(32) for j in range(nw)])
    got = np.asarray(planar_columns(x, block_k))
    np.testing.assert_array_equal(got, np.asarray(x)[:, src])
    back = np.empty_like(got)
    back[:, src] = got
    np.testing.assert_array_equal(back, np.asarray(x))


def test_decode_dispatch_matches_matmul_blocking():
    """tiled_dense_infer at decode m equals the same call forced through
    the reference math — the dispatch changes blocking, not results."""
    spec = plan_tiling((256, 64), p=4, min_size=1, alpha_source="W")
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 64))
    t = jnp.where(jax.random.bernoulli(jax.random.PRNGKey(1), 0.5,
                                       (spec.rows_per_tile, 64)), 1.0, -1.0)
    rows = pack_bits(t)                          # row-packed serve form
    alpha = jnp.abs(jax.random.normal(jax.random.PRNGKey(2), (4,))) + 0.1
    got = tiled_dense_infer(x, rows, alpha, spec, use_pallas=True)
    want = tiled_dense_infer(x, rows, alpha, spec, use_pallas=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


# --------------------------------------------------------------------------
# tile_construct kernel
# --------------------------------------------------------------------------
@pytest.mark.parametrize("p,q", [(2, 64), (4, 128), (8, 4096), (4, 8192), (3, 96)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_tile_construct_pallas_matches_ref(p, q, dtype):
    w = jax.random.normal(jax.random.PRNGKey(p * q), (p, q), dtype)
    bq = min(1024, q)
    got_packed, got_alpha = tile_construct_pallas(w, block_q=bq, interpret=True)
    want_packed, want_alpha = tile_construct_ref(w.astype(jnp.float32))
    np.testing.assert_array_equal(np.asarray(got_packed), np.asarray(want_packed))
    rtol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(got_alpha), np.asarray(want_alpha), rtol=rtol)


@pytest.mark.parametrize("alpha_source", ["W", "A"])
@pytest.mark.parametrize("alpha_mode", ["layer", "tile"])
def test_tile_construct_wrapper_matches_core(alpha_source, alpha_mode):
    from repro.core import compute_alpha, tile_vector

    spec = plan_tiling(
        (40, 50), p=4, min_size=1, alpha_mode=alpha_mode, alpha_source=alpha_source
    )  # q = 500: not a multiple of 32 -> exercises padding
    kw, ka = jax.random.split(jax.random.PRNGKey(3))
    w = jax.random.normal(kw, (40, 50))
    a = jax.random.normal(ka, (40, 50))
    for use_pallas in (False, True):
        packed, alpha = tile_construct(w, spec, a=a, use_pallas=use_pallas)
        t = unpack_bits(packed, spec.q)
        np.testing.assert_array_equal(np.asarray(t), np.asarray(tile_vector(w, spec)))
        src = a if alpha_source == "A" else w
        np.testing.assert_allclose(
            np.asarray(alpha), np.asarray(compute_alpha(src, spec)), rtol=1e-5
        )


def test_construct_with_separate_alpha_source():
    w = jax.random.normal(jax.random.PRNGKey(4), (4, 256))
    a = jax.random.normal(jax.random.PRNGKey(5), (4, 256))
    _, alpha_w = tile_construct_pallas(w, interpret=True)
    _, alpha_a = tile_construct_pallas(w, a, interpret=True)
    np.testing.assert_allclose(
        np.asarray(alpha_a), np.abs(np.asarray(a)).mean(1), rtol=1e-5
    )
    assert not np.allclose(np.asarray(alpha_w), np.asarray(alpha_a))


# --------------------------------------------------------------------------
# fused training forward
# --------------------------------------------------------------------------
@pytest.mark.parametrize("alpha_source", ["W", "A"])
def test_tbn_dense_train_forward_and_grad_match_reference(alpha_source):
    from repro.core import tiled_weight

    spec = plan_tiling(
        (64, 48), p=4, min_size=1, alpha_mode="tile", alpha_source=alpha_source
    )
    kx, kw, ka = jax.random.split(jax.random.PRNGKey(6), 3)
    x = jax.random.normal(kx, (10, 48))
    w = jax.random.normal(kw, (64, 48))
    a = jax.random.normal(ka, (64, 48)) if alpha_source == "A" else w

    def ref(x, w, a):
        bhat = tiled_weight(w, spec, a=(a if alpha_source == "A" else None))
        return jnp.einsum("mk,ok->mo", x, bhat)

    y_ref = ref(x, w, a)
    y_fused = tbn_dense_train(x, w, a, spec)
    np.testing.assert_allclose(np.asarray(y_fused), np.asarray(y_ref), rtol=1e-4, atol=1e-4)

    gref = jax.grad(lambda w, a: (ref(x, w, a) ** 2).sum(), argnums=(0, 1))(w, a)
    gfused = jax.grad(
        lambda w, a: (tbn_dense_train(x, w, a, spec) ** 2).sum(), argnums=(0, 1)
    )(w, a)
    for g1, g2 in zip(gref, gfused):
        np.testing.assert_allclose(np.asarray(g2), np.asarray(g1), rtol=1e-3, atol=1e-4)


