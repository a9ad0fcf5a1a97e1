"""The Pallas segment-sum kernel (sparse hop scatter-add) vs the jnp
oracle, the backend probe behind every kernel entry point, and the
executor-level kernel route.

Unlike :mod:`tests.test_kernels` (which needs hypothesis and skips when
it is absent), these run everywhere: the segment-sum kernel backs the
sparse executors' innermost hop, so its parity must be part of tier-1.
All kernel executions here use ``interpret=True`` — this container is
CPU-only, which is exactly what :func:`repro.kernels.ops
.default_interpret` resolves to.
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.kernels import ops, ref, segsum_kernel


# ------------------------------------------------------------ segment-sum ---
@pytest.mark.parametrize("n,d,p", [(10, 1, 4), (513, 16, 300),
                                   (800, 7, 1000), (2048, 64, 2048)])
def test_segsum_rows_kernel_matches_ref(n, d, p):
    rng = np.random.default_rng(n + d + p)
    # +3: ids past the segment space, like the executor's edge-bucket pads
    seg = jnp.asarray(rng.integers(0, p + 3, size=n, dtype=np.int32))
    rows = jnp.asarray(rng.uniform(0, 2, size=(n, d)).astype(np.float32))
    got = ops.edge_segment_sum(seg, rows, p, interpret=True)
    want = ref.edge_segment_sum_ref(seg, rows, p)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-3)


@pytest.mark.parametrize("n,p", [(10, 4), (513, 300), (4096, 1024)])
def test_segsum_ones_kernel_matches_ref(n, p):
    rng = np.random.default_rng(n + p)
    seg = jnp.asarray(rng.integers(0, p + 3, size=n, dtype=np.int32))
    w = jnp.asarray(rng.uniform(0, 2, size=n).astype(np.float32))
    got = ops.ones_segment_sum(seg, w, p, interpret=True)
    want = ref.ones_segment_sum_ref(seg, w, p)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-3)


def test_segsum_kernel_drops_padding_and_weights():
    # executor invariant: pad edges scatter to seg == num_segments and
    # the sharded mesh pads carry weight 0 — neither may leak into counts
    seg = jnp.asarray(np.array([0, 3, 1, 3, 2], np.int32))   # 3 == P: pad
    w = jnp.asarray(np.array([1.0, 5.0, 1.0, 5.0, 0.0], np.float32))
    got = ops.ones_segment_sum(seg, w, 3, interpret=True)
    np.testing.assert_allclose(np.asarray(got), [1.0, 1.0, 0.0])


@pytest.mark.parametrize("seed", [0, 7, 42, 1234])
def test_segsum_kernel_random_shapes(seed):
    rng = np.random.default_rng(seed)
    for _ in range(5):
        n = int(rng.integers(1, 600))
        d = int(rng.integers(1, 48))
        p = int(rng.integers(1, 700))
        seg = jnp.asarray(rng.integers(0, p + 2, size=n, dtype=np.int32))
        rows = jnp.asarray(rng.uniform(0, 3, size=(n, d)).astype(np.float32))
        np.testing.assert_allclose(
            np.asarray(ops.edge_segment_sum(seg, rows, p, interpret=True)),
            np.asarray(ref.edge_segment_sum_ref(seg, rows, p)),
            rtol=1e-5, atol=1e-3,
            err_msg=f"seed={seed} n={n} d={d} p={p}")


# ------------------------------------------------ blocked (parent-sorted) ---
def _blocked_layout(rng, tiles, slots, ds):
    """A random parent-sorted slot layout: parents with no edge, a tile
    filled to exactly ``slots``, padding slots holding junk at weight 0,
    and a parent of degree 300 in tile 0, past bfloat16's exact 256."""
    tp = segsum_kernel.TILE_PARENTS
    parent = rng.integers(0, tp, (tiles, slots)).astype(np.int32)
    code = rng.integers(0, ds, (tiles, slots)).astype(np.int32)
    weight = np.zeros((tiles, slots), np.float32)
    for t in range(tiles):
        fill = slots if t == tiles - 1 else int(rng.integers(320, slots))
        deg = np.bincount(rng.integers(0, tp // 2, fill), minlength=tp)
        if t == 0:
            deg[:] = 0
            deg[7] = 300
            deg[9] = fill - 300
        parent[t, :fill] = rng.permutation(np.repeat(np.arange(tp), deg))
        weight[t, :fill] = 1.0
    return parent, code, weight


def _blocked_oracle(parent, code, weight, ds):
    tiles = parent.shape[0]
    tp = segsum_kernel.TILE_PARENTS
    ids = (np.arange(tiles)[:, None] * tp + parent) * ds + code
    flat = jax.ops.segment_sum(jnp.asarray(weight.ravel()),
                               jnp.asarray(ids.ravel()),
                               num_segments=tiles * tp * ds)
    return np.asarray(flat).reshape(tiles * tp, ds)


@pytest.mark.parametrize("ds", [1, 9, 27])
@pytest.mark.parametrize("tiles,slots", [(3, 512), (16, 640)])
def test_blocked_kernel_equals_a_segment_sum(tiles, slots, ds):
    """Exact: every count is an integer, and tile 0 holds a parent of
    degree 300 (one pass of bfloat16 sums would round it).  Sixteen tiles
    take two grid steps; three take one step of three rows."""
    rng = np.random.default_rng(tiles * 100 + ds)
    parent, code, weight = _blocked_layout(rng, tiles, slots, ds)
    want = _blocked_oracle(parent, code, weight, ds)
    assert want[7].sum() == 300
    n_parent = tiles * segsum_kernel.TILE_PARENTS - 5
    got = ops.blocked_ones_segment_sum(
        jnp.asarray(parent), jnp.asarray(code), jnp.asarray(weight),
        n_parent, ds, interpret=True)
    assert got.shape == (n_parent, ds)
    np.testing.assert_array_equal(np.asarray(got), want[:n_parent])
    assert (np.asarray(got).sum(axis=1) == 0).any()      # empty parents


def test_blocked_kernel_refuses_a_ragged_grid():
    z = jnp.zeros((12, 128), jnp.int32)
    with pytest.raises(ValueError, match="no multiple"):
        segsum_kernel.blocked_segment_sum_pallas(
            z, z, z.astype(jnp.float32), 3, interpret=True)


# ------------------------------------------------- backend probe / routing ---
def test_default_interpret_probe_and_env_override(monkeypatch):
    monkeypatch.delenv("REPRO_PALLAS_INTERPRET", raising=False)
    # this container is CPU-only: the probe must choose the interpreter
    assert jax.default_backend() == "cpu"
    assert ops.default_interpret() is True
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    assert ops.default_interpret() is False          # forced native
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "true")
    assert ops.default_interpret() is True
    monkeypatch.delenv("REPRO_PALLAS_INTERPRET")
    assert ops.default_interpret() is True


def test_segsum_kernel_routing_predicate(monkeypatch):
    monkeypatch.delenv("REPRO_SEGSUM_PALLAS", raising=False)
    # CPU default: XLA scatter wins, kernel stays off
    assert ops.segsum_kernel_enabled(256) is False
    monkeypatch.setenv("REPRO_SEGSUM_PALLAS", "1")
    assert ops.segsum_kernel_enabled(256) is True
    # the O(edges x segments) one-hot sweep is always capped
    assert ops.segsum_kernel_enabled(
        ops.SEGSUM_KERNEL_MAX_SEGMENTS + 1) is False
    monkeypatch.setenv("REPRO_SEGSUM_PALLAS", "0")
    assert ops.segsum_kernel_enabled(256) is False


def test_blocked_kernel_routing_predicate(monkeypatch):
    monkeypatch.delenv("REPRO_SEGSUM_PALLAS", raising=False)
    big = ops.BLOCKED_MIN_EDGES
    # CPU default: XLA scatter
    assert ops.blocked_segsum_enabled(big, 9) is False
    monkeypatch.setattr(ops, "_on_accelerator", lambda: True)
    assert ops.blocked_segsum_enabled(big, 9) is True
    assert ops.blocked_segsum_enabled(big - 1, 9) is False   # delta views
    assert ops.blocked_segsum_enabled(big, ops.BLOCKED_MAX_CODES) is True
    assert ops.blocked_segsum_enabled(
        big, ops.BLOCKED_MAX_CODES + 1) is False
    monkeypatch.setenv("REPRO_SEGSUM_PALLAS", "0")
    assert ops.blocked_segsum_enabled(big, 9) is False
    monkeypatch.setattr(ops, "_on_accelerator", lambda: False)
    monkeypatch.setenv("REPRO_SEGSUM_PALLAS", "1")
    assert ops.blocked_segsum_enabled(10, 9) is True        # any edges
    assert ops.blocked_segsum_enabled(
        10, ops.BLOCKED_MAX_CODES + 1) is False


def test_sparse_executor_kernel_route_parity(monkeypatch):
    """Counts through the kernel-backed scatter-add (forced on, interpret
    mode) are bit-identical to the XLA segment-sum path."""
    from repro.core import CostStats, CountingEngine, build_lattice
    from tests.test_counting_core import tiny_db

    db = tiny_db(4)
    points = build_lattice(db.schema, 2)
    monkeypatch.delenv("REPRO_SEGSUM_PALLAS", raising=False)
    eng = CountingEngine(db, "sparse", CostStats())
    want = [np.asarray(eng.contract(p, None).counts) for p in points]
    monkeypatch.setenv("REPRO_SEGSUM_PALLAS", "1")
    eng_k = CountingEngine(db, "sparse", CostStats())
    for p, w in zip(points, want):
        np.testing.assert_array_equal(
            np.asarray(eng_k.contract(p, None).counts), w,
            err_msg=str(p))
