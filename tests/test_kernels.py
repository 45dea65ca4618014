"""Pallas kernel sweeps (interpret mode) vs pure-jnp oracles."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.bitpack import pack_matrix
from repro.kernels import ops, ref

RNG = np.random.default_rng(0)

WORD_SHAPES = [(1, 32), (3, 100), (8, 1024), (16, 2048), (20, 1500), (64, 96)]


@pytest.mark.parametrize("shape", WORD_SHAPES)
@pytest.mark.parametrize("op", ["and", "or", "xor", "andnot"])
def test_word_logical_sweep(shape, op):
    a = RNG.integers(0, 2**32, size=shape, dtype=np.uint32)
    b = RNG.integers(0, 2**32, size=shape, dtype=np.uint32)
    a[0, :] = 0  # force clean-zero tiles
    if shape[0] > 2:
        b[2, :] = 0xFFFFFFFF  # clean-one tiles
    got = np.asarray(ops.word_logical(a, b, op))
    want = np.asarray(ref.word_logical(jnp.asarray(a), jnp.asarray(b), op))
    assert np.array_equal(got, want)


def test_word_logical_all_clean_tiles():
    a = np.zeros((8, 1024), np.uint32)
    b = np.full((8, 1024), 0xFFFFFFFF, np.uint32)
    assert np.asarray(ops.word_logical(a, b, "or")).min() == 0xFFFFFFFF
    assert np.asarray(ops.word_logical(a, b, "and")).max() == 0


@pytest.mark.parametrize("flags", [False, True])
@pytest.mark.parametrize("L", [1, 2, 3, 7, 8, 9, 16, 17, 25, 33])
@pytest.mark.parametrize("op", ["and", "or", "xor"])
def test_logical_reduce_matches_numpy(L, op, flags):
    # 3000 words pad to a 4096-word bucket: four 1024-word blocks a row,
    # each random, all zeros or all ones (CLEAN0 and CLEAN1 tiles); block 1
    # is all ones in every row, so AND keeps it CLEAN1 through every round
    # and OR/XOR see only clean operands there
    rng = np.random.default_rng(L)
    cols, width = 3000, ops.bucket_cols(3000)
    mat = rng.integers(0, 2**32, size=(L, width), dtype=np.uint32)
    kind = rng.choice(3, size=(L, width // 1024), p=[0.4, 0.3, 0.3])
    kind[:, 1] = 2
    blocks = mat.reshape(L, -1, 1024)
    blocks[kind == 1] = 0
    blocks[kind == 2] = 0xFFFFFFFF
    mat = mat[:, :cols]
    rf = None
    if flags:
        rf = ops.np_row_flags(np.pad(mat, ((0, 0), (0, width - cols))))
        assert {int(f) for f in np.unique(rf)} == {0, 1, 2}
    got = np.asarray(ops.logical_reduce(mat, op=op, row_flags=rf))
    npop = {"and": np.bitwise_and, "or": np.bitwise_or,
            "xor": np.bitwise_xor}[op]
    want = npop.reduce(mat, axis=0)
    assert np.array_equal(got, want)


@pytest.mark.parametrize("shape", [(1, 5), (8, 1024), (5, 333), (17, 2049)])
def test_popcount_sweep(shape):
    a = RNG.integers(0, 2**32, size=shape, dtype=np.uint32)
    assert int(ops.popcount_total(a)) == int(ref.popcount_total(jnp.asarray(a)))
    np.testing.assert_array_equal(np.asarray(ops.popcount_rows(a)),
                                  np.asarray(ref.popcount_rows(jnp.asarray(a))))


@pytest.mark.parametrize("N,L", [(32, 4), (1024, 128), (2048, 200), (96, 7),
                                 (4096, 64)])
@pytest.mark.parametrize("density", [0.02, 0.5])
def test_bitpack_sweep(N, L, density):
    bits = RNG.random((N, L)) < density
    got = np.asarray(ops.bitpack(bits))
    want = np.asarray(ref.bitpack(jnp.asarray(bits)))
    assert np.array_equal(got, want)
    # convention matches the host codec (bit i of word w = row 32w+i)
    assert np.array_equal(got.T, pack_matrix(bits))


@pytest.mark.parametrize("n", [256, 256 * 100, 256 * 100 + 17])
@pytest.mark.parametrize("dtype", [np.float32, np.float16])
def test_block_sqnorms_sweep(n, dtype):
    g = RNG.standard_normal(n).astype(dtype)
    got = np.asarray(ops.block_sqnorms(g))
    pad = (-len(g)) % 256
    gp = np.pad(g.astype(np.float32), (0, pad))
    want = np.asarray(ref.block_sqnorms(jnp.asarray(gp), 256))
    np.testing.assert_allclose(got, want, rtol=2e-3)


def test_topk_block_mask():
    g = np.zeros(256 * 10, np.float32)
    g[256 * 3: 256 * 4] = 100.0  # one hot block
    mask = np.asarray(ops.topk_block_mask(g, 0.1))
    assert mask[3] and mask.sum() == 1


def test_interpret_mode_follows_backend(monkeypatch):
    assert ops.interpret_mode() is True  # the tests run on the CPU backend
    for backend, want in (("cpu", True), ("tpu", False)):
        monkeypatch.setattr(ops.jax, "default_backend", lambda b=backend: b)
        assert ops.interpret_mode() is want
    monkeypatch.setattr(ops.jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="gpu"):
        ops.interpret_mode()
    with pytest.raises(RuntimeError):
        ops.popcount_total(np.ones((8, 1024), np.uint32))


@pytest.fixture
def restore_cache_config():
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    prev = {k: getattr(ops.jax.config, k) for k in keys}
    yield
    for k, v in prev.items():
        ops.jax.config.update(k, v)


def test_compile_cache_defaults_to_checkout(monkeypatch,
                                            restore_cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = ops.use_compile_cache()
    checkout = __import__("pathlib").Path(__file__).resolve().parents[1]
    assert path == str(checkout / ".jax_cache")
    assert ops.jax.config.jax_compilation_cache_dir == path
    # kernels compile in well under a second: nothing may gate them out
    assert ops.jax.config.jax_persistent_cache_min_compile_time_secs == 0


def test_compile_cache_env_wins(tmp_path, monkeypatch, restore_cache_config):
    outside = str(tmp_path / "outside")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", outside)
    before = ops.jax.config.jax_compilation_cache_dir
    assert ops.use_compile_cache() == outside
    # JAX reads the variable itself; the code sets no other directory
    assert ops.jax.config.jax_compilation_cache_dir == before
