import numpy as np
import jax.numpy as jnp
import pytest
from tests.hypothesis_compat import given, settings, st

from repro.core import ops
from repro.graph.csr import time_index


@settings(max_examples=40, deadline=None)
@given(
    data=st.data(),
    n=st.integers(1, 60),
)
def test_lower_bound_matches_searchsorted(data, n):
    vals = sorted(data.draw(st.lists(st.integers(0, 100), min_size=n, max_size=n)))
    flat = jnp.asarray(np.array(vals, dtype=np.int32))
    lo = data.draw(st.integers(0, n - 1))
    hi = data.draw(st.integers(lo, n))
    qs = np.array(
        data.draw(st.lists(st.integers(-5, 105), min_size=5, max_size=5)),
        dtype=np.int32,
    )
    got = ops.lower_bound(
        flat, jnp.int32(lo), jnp.int32(hi), jnp.asarray(qs), ops.n_iters_for(n)
    )
    want = lo + np.searchsorted(np.asarray(vals)[lo:hi], qs, side="left")
    np.testing.assert_array_equal(np.asarray(got), want)


def test_count_id_in_window_brute():
    rng = np.random.default_rng(0)
    n_rows, max_len = 8, 20
    rows = []
    for _ in range(n_rows):
        k = rng.integers(0, max_len)
        ids = np.sort(rng.integers(0, 6, k))
        ts = np.zeros(k, dtype=np.int64)
        # times sorted within each id run
        for v in np.unique(ids):
            m = ids == v
            ts[m] = np.sort(rng.integers(0, 50, m.sum()))
        rows.append((ids.astype(np.int32), ts.astype(np.int32)))
    indptr = np.zeros(n_rows + 1, dtype=np.int32)
    for i, (ids, _) in enumerate(rows):
        indptr[i + 1] = indptr[i] + len(ids)
    nbr = np.concatenate([r[0] for r in rows]) if rows else np.zeros(0, np.int32)
    tt = np.concatenate([r[1] for r in rows])

    node = rng.integers(0, n_rows, 30).astype(np.int32)
    x = rng.integers(-1, 6, 30).astype(np.int32)
    after = rng.integers(-5, 40, 30).astype(np.int32)
    until = after + rng.integers(0, 30, 30).astype(np.int32)
    got = ops.count_id_in_window(
        jnp.asarray(nbr),
        jnp.asarray(tt),
        jnp.asarray(indptr),
        jnp.asarray(node),
        jnp.asarray(x),
        jnp.asarray(after),
        jnp.asarray(until),
        ops.n_iters_for(max_len),
    )
    want = []
    for nd, xx, a, u in zip(node, x, after, until):
        ids, ts = rows[nd]
        want.append(
            0 if xx < 0 else int(np.sum((ids == xx) & (ts > a) & (ts <= u)))
        )
    np.testing.assert_array_equal(np.asarray(got), want)


def test_expand_mask_and_offset():
    indptr = jnp.asarray(np.array([0, 3, 3, 7], dtype=np.int32))
    flat = jnp.asarray(np.arange(7, dtype=np.int32) * 10)
    node = jnp.asarray(np.array([0, 1, 2, -1], dtype=np.int32))
    mask, vals = ops.expand(indptr, (flat,), node, 4)
    np.testing.assert_array_equal(
        np.asarray(mask),
        [[True, True, True, False], [False] * 4, [True] * 4, [False] * 4],
    )
    np.testing.assert_array_equal(np.asarray(vals)[0, :3], [0, 10, 20])
    # offset sweeps the tail of row 2 (len 4): offset 4 -> nothing left
    mask2, _ = ops.expand(indptr, (flat,), node, 4, offset=4)
    assert not np.asarray(mask2)[2].any()


def _tiled_csr(rng, tile):
    """Time-sorted CSR rows for the tiled search: empty rows, rows that
    cross tile and sample boundaries, one row longer than tile**2, five
    trailing isolated nodes, and a flat whose length is no multiple of
    the tile."""
    lens = rng.integers(0, 6 * tile, 48)
    lens[rng.choice(48, 8, replace=False)] = 0
    lens[17] = tile * tile + 3
    lens = np.concatenate([lens, np.zeros(5, np.int64)])
    if lens.sum() % tile == 0:
        lens[3] += 1
    indptr = np.concatenate([[0], np.cumsum(lens)]).astype(np.int32)
    flat = np.concatenate(
        [np.sort(rng.integers(0, 1000, n)) for n in lens]
    ).astype(np.int32)
    return indptr, flat


@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("tile", [2, 4, 128])
def test_count_window_tiled_matches_binary_search_and_brute(tile, seed):
    rng = np.random.default_rng(seed)
    indptr, flat = _tiled_csr(rng, tile)
    index = time_index(flat, tile)
    assert len(index) >= 3 and index[-1].shape[1] == tile
    assert len(flat) % tile != 0 and int(np.diff(indptr).max()) > tile * tile
    n_nodes = len(indptr) - 1
    q = 400
    node = rng.integers(-1, n_nodes, q).astype(np.int32)
    node[:4] = [-1, 17, n_nodes - 1, 17]
    after = rng.integers(-50, 1100, q).astype(np.int32)
    until = (after + rng.integers(-20, 300, q)).astype(np.int32)
    # windows wholly before and wholly after every row
    after[4:8], until[4:8] = -100, -1
    after[8:12], until[8:12] = 1000, 2000
    node[4:12] = [17, 0, 1, 2, 17, 0, 1, 2]

    got = ops.count_window_tiled(
        tuple(jnp.asarray(x) for x in index),
        jnp.asarray(indptr),
        jnp.asarray(node),
        jnp.asarray(after),
        jnp.asarray(until),
    )
    binary = ops.count_window(
        jnp.asarray(flat),
        jnp.asarray(indptr),
        jnp.asarray(node),
        jnp.asarray(after),
        jnp.asarray(until),
        ops.n_iters_for(int(np.diff(indptr).max())),
    )
    want = []
    for nd, a, u in zip(node, after, until):
        row = flat[indptr[max(nd, 0)] : indptr[max(nd, 0) + 1]]
        want.append(0 if nd < 0 else int(np.sum((row > a) & (row <= u))))
    np.testing.assert_array_equal(np.asarray(got), want)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(binary))

    # the lower bound itself, as an absolute flat position
    safe = np.maximum(node, 0)
    start, end = indptr[safe], indptr[safe + 1]
    lb = ops.tiled_lower_bound(
        tuple(jnp.asarray(x) for x in index),
        jnp.asarray(start),
        jnp.asarray(end),
        jnp.asarray(after),
    )
    want_lb = [
        s + np.searchsorted(flat[s:e], v, side="left")
        for s, e, v in zip(start, end, after)
    ]
    np.testing.assert_array_equal(np.asarray(lb), want_lb)
