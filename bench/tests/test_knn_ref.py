"""The kNN reference's check, deduplicated by (query, served ids) and
computed in blocks, against the same check made answer by answer in one
piece."""
import jax.numpy as jnp
import numpy as np
import pytest

from bench.refs import knn


def _exact_whole(rows, qcodes, ids, distance):
    """float64 distances of every answer at once (no blocks)."""
    K = rows.shape[0]
    ids = np.asarray(ids, np.int64)
    ok = (ids >= 0) & (ids < K)
    dup = np.zeros(ids.shape, bool)
    for j in range(1, ids.shape[1]):
        dup[:, j] = (ids[:, :j] == ids[:, j:j + 1]).any(axis=1)
    safe = np.where(ok, ids, 0)
    g = np.asarray(jnp.take(rows, jnp.asarray(safe.reshape(-1)), axis=0),
                   np.float64).reshape(*ids.shape, -1)
    q = np.asarray(qcodes, np.float64)[:, None, :]
    d = ((g - q) ** 2).sum(-1) if distance == "l2" else -(g * q).sum(-1)
    return np.where(ok & ~dup, d, np.inf)


def _gaps_per_answer(rows, qcodes, cand, qidx, served, distance):
    """Every answer checked on its own, with no deduplication."""
    k = served.shape[1]
    qn = np.asarray(qcodes)
    d_ref = np.sort(_exact_whole(rows, qn, cand, distance), axis=1)[:, :k]
    d_srv = np.sort(_exact_whole(rows, qn[qidx], served, distance), axis=1)
    with np.errstate(invalid="ignore"):
        g = d_srv - d_ref[qidx]
    g = np.where(np.isnan(g), np.inf, g)
    return g.max(axis=1)


def _answers(rng, cand, far, K, k, copies):
    """Each query's best answer served ``copies`` times in a shuffled
    stream, with one bad copy (its last id replaced by the row ``far``),
    one answer in another order, an invalid id, a repeated id, and random
    answers."""
    U = cand.shape[0]
    qidx = np.repeat(np.arange(U), copies)
    served = cand[qidx, :k].copy()
    served[copies // 2, -1] = far                         # the bad copy
    served[copies + 1] = served[copies + 1][::-1]         # another order
    served[2 * copies + 1, 0] = K                         # out of range
    served[2 * copies + 2, 1] = served[2 * copies + 2, 0]  # repeated id
    tail = rng.integers(0, K, (40, k))
    qidx = np.concatenate([qidx, rng.integers(0, U, 40)])
    served = np.concatenate([served, tail])
    p = rng.permutation(len(qidx))
    return qidx[p], served[p].astype(np.int32), np.argsort(p)


@pytest.mark.parametrize("gather_rows", [knn.GATHER_ROWS, 37, 1])
@pytest.mark.parametrize("distance", ["l2", "dot"])
def test_deduplicated_blocked_check_reads_as_answer_by_answer(
        monkeypatch, distance, gather_rows):
    monkeypatch.setattr(knn, "GATHER_ROWS", gather_rows)
    rng = np.random.default_rng(17)
    K, N, U, k, copies = 300, 12, 20, 10, 25
    rows = jnp.asarray(np.round(rng.normal(3, 2, (K, N))), jnp.float32)
    qcodes = jnp.asarray(np.round(rng.normal(3, 2, (U, N))), jnp.float32)
    cand = knn.all_candidates(rows, qcodes, distance=distance,
                              n_cand=k + knn.N_CAND_EXTRA)
    far = int(np.argmax(_exact_whole(rows, qcodes[:1], np.arange(K)[None],
                                     distance)))
    qidx, served, where = _answers(rng, cand, far, K, k, copies)
    got = knn.gaps(rows, qcodes, cand, qidx, served, distance)
    want = _gaps_per_answer(rows, qcodes, cand, qidx, served, distance)
    assert got.shape == (len(qidx),)
    assert np.array_equal(got, want)
    by_input = got[where]                  # in the order _answers made them
    assert by_input[copies // 2] > 0       # the one bad copy reads a gap
    assert np.all(np.delete(by_input[:copies], copies // 2) == 0)
    assert by_input[copies + 1] == 0       # order does not matter
    assert np.isinf(by_input[2 * copies + 1])
    assert np.isinf(by_input[2 * copies + 2])


def test_exact_blocks_match_the_whole_computation(monkeypatch):
    rng = np.random.default_rng(5)
    rows = jnp.asarray(rng.normal(size=(50, 7)), jnp.float32)
    q = rng.normal(size=(9, 7)).astype(np.float32)
    ids = rng.integers(-2, 53, (9, 4))
    want = _exact_whole(rows, q, ids, "l2")
    monkeypatch.setattr(knn, "GATHER_ROWS", 6)
    assert np.array_equal(knn.exact(rows, q, ids, "l2"), want)
    qidx = rng.integers(0, 9, 30)
    ids = rng.integers(0, 50, (30, 4))
    assert np.array_equal(knn.exact(rows, q, ids, "dot", qidx=qidx),
                          _exact_whole(rows, q[qidx], ids, "dot"))
