"""Runtime: checkpoint/restart, fault supervision, elastic re-shard,
gradient compression, sharding resolver, data pipeline."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, strategies as st

from repro.checkpoint import committed_steps, restore, save
from repro.configs import get_config
from repro.data import SyntheticLM
from repro.optim import (AdamW, constant, dequantize_int8, ef_compress,
                         init_error_state, quantize_int8)
from repro.runtime import (ShardingRules, init_state, make_train_step,
                           state_axes)
from repro.runtime.fault import StepFailure, Supervisor

KEY = jax.random.PRNGKey(0)


# ---------------------------------------------------------------------------
# checkpointing
# ---------------------------------------------------------------------------
def test_checkpoint_roundtrip(tmp_path):
    tree = {"a": jnp.arange(10.0), "b": {"c": jnp.ones((3, 4), jnp.bfloat16),
                                         "d": jnp.int32(7)}}
    save(str(tmp_path), 5, tree)
    step, back = restore(str(tmp_path), tree)
    assert step == 5
    jax.tree_util.tree_map(
        lambda x, y: np.testing.assert_array_equal(
            np.asarray(x, np.float32), np.asarray(y, np.float32)),
        tree, back)


def test_checkpoint_keep_n_and_commit_marker(tmp_path):
    tree = {"x": jnp.zeros(3)}
    for s in (1, 2, 3, 4):
        save(str(tmp_path), s, tree, keep=2)
    assert committed_steps(str(tmp_path)) == [3, 4]
    # torn checkpoint (no marker) is ignored
    os.makedirs(tmp_path / "step_00000009")
    assert committed_steps(str(tmp_path)) == [3, 4]
    step, _ = restore(str(tmp_path), tree)
    assert step == 4


def test_checkpoint_structure_mismatch_rejected(tmp_path):
    save(str(tmp_path), 1, {"a": jnp.zeros(2)})
    with pytest.raises(ValueError, match="structure mismatch"):
        restore(str(tmp_path), {"a": jnp.zeros(2), "b": jnp.zeros(2)})


# ---------------------------------------------------------------------------
# fault supervision (injected failures + stragglers)
# ---------------------------------------------------------------------------
def test_supervisor_restart_resumes_from_checkpoint(tmp_path):
    calls = {"n": 0}

    def step_fn(state, batch):
        return {"v": state["v"] + batch}, {"loss": state["v"]}

    def batch_fn(step):
        return jnp.float32(1.0)

    fail_at = {12}

    def fault_hook(step):
        if step in fail_at and calls["n"] < 50:
            fail_at.discard(step)
            raise StepFailure("injected node failure")
        calls["n"] += 1

    sup = Supervisor(step_fn=step_fn, batch_fn=batch_fn,
                     ckpt_dir=str(tmp_path), ckpt_every=5,
                     fault_hook=fault_hook)
    final_step, state = sup.run({"v": jnp.float32(0.0)}, 0, 20)
    assert final_step == 20
    assert sup.restarts == 1
    assert any(e.startswith("restore@") for e in sup.events)
    # deterministic data => same final value as a clean run
    assert float(state["v"]) == 20.0


def test_supervisor_straggler_detection(tmp_path):
    import time as _t
    times = iter([0.01] * 10 + [0.3] + [0.01] * 5)

    def step_fn(state, batch):
        _t.sleep(next(times, 0.01))
        return state, {}

    sup = Supervisor(step_fn=step_fn, batch_fn=lambda s: None,
                     ckpt_dir=str(tmp_path), ckpt_every=100,
                     straggler_factor=3.0)
    sup.run({}, 0, 16)
    assert any("straggler@" in e for e in sup.events)


# ---------------------------------------------------------------------------
# elastic re-shard (checkpoint written on one mesh, restored on another)
# ---------------------------------------------------------------------------
def test_elastic_restore_across_mesh_shapes(tmp_path):
    from repro.runtime import elastic
    cfg = get_config("qwen2-1.5b").reduced()
    opt = AdamW(lr=constant(1e-3))
    state = init_state(KEY, cfg, opt)
    save(str(tmp_path), 3, state)
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    step, restored = elastic.elastic_restore(
        str(tmp_path), state, state_axes(cfg), mesh)
    assert step == 3
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(
            np.asarray(a, np.float32), np.asarray(b, np.float32)),
        state.params, restored.params)


# ---------------------------------------------------------------------------
# gradient compression
# ---------------------------------------------------------------------------
@given(st.integers(0, 10 ** 6))
@settings(max_examples=20, deadline=None)
def test_int8_quantization_bounded_error(seed):
    x = jax.random.normal(jax.random.PRNGKey(seed), (64,)) * 3.0
    q, scale = quantize_int8(x)
    err = np.abs(np.asarray(dequantize_int8(q, scale) - x))
    assert err.max() <= float(scale) * 0.5 + 1e-6


def test_error_feedback_converges():
    """EF compression: the running mean of dequantized grads approaches
    the true mean (bias -> 0 over steps)."""
    g = jax.random.normal(jax.random.PRNGKey(0), (256,))
    err = jnp.zeros_like(g)
    total = jnp.zeros_like(g)
    n = 50
    for _ in range(n):
        q, s, err = ef_compress(g, err)
        total = total + dequantize_int8(q, s)
    np.testing.assert_allclose(np.asarray(total / n), np.asarray(g),
                               atol=2e-3)


def test_compressed_psum_shard_map():
    devs = jax.devices()
    mesh = jax.make_mesh((len(devs),), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,) * 1)
    from jax.sharding import PartitionSpec as P
    from repro.optim import compressed_psum

    grads = {"w": jax.random.normal(KEY, (8, 16))}
    errs = init_error_state(grads)

    def body(g, e):
        return compressed_psum(g, e, "data")

    out, new_err = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(P(), P()), out_specs=(P(), P())))(
        grads, errs)
    np.testing.assert_allclose(np.asarray(out["w"]),
                               np.asarray(grads["w"]), atol=0.05)


# ---------------------------------------------------------------------------
# CAM search serving (micro-batching over the store-once simulators)
# ---------------------------------------------------------------------------
def test_cam_search_server_batches_and_matches_direct_query():
    from repro.core import (AppConfig, ArchConfig, CAMConfig, CircuitConfig,
                            DeviceConfig, FunctionalSimulator)
    from repro.runtime import CAMSearchServer

    cfg = CAMConfig(
        app=AppConfig(distance="l2", match_type="best", match_param=2,
                      data_bits=3),
        arch=ArchConfig(h_merge="adder", v_merge="comparator"),
        circuit=CircuitConfig(rows=8, cols=8, cell_type="mcam",
                              sensing="best"),
        device=DeviceConfig(device="fefet"))
    sim = FunctionalSimulator(cfg)
    stored = jax.random.uniform(KEY, (30, 16))
    queries = np.asarray(jax.random.uniform(jax.random.PRNGKey(1),
                                            (11, 16)))
    state = sim.write(stored)
    srv = CAMSearchServer(sim, state, batch=4)
    reqs = [srv.submit(q) for q in queries]
    assert srv.step() == 4                 # one full batch
    assert reqs[3].done and not reqs[4].done
    done = srv.run()
    assert len(done) == 11 and all(r.done for r in reqs)
    # answers equal the direct batched query (no variation => key-free)
    idx, mask = sim.query(state, jnp.asarray(queries))
    for i, r in enumerate(done):
        assert r.rid == i
        np.testing.assert_array_equal(r.indices, np.asarray(idx[i]))
        np.testing.assert_array_equal(r.mask, np.asarray(mask[i]))


def _cam_server_cfg(variation: str = "none"):
    from repro.core import (AppConfig, ArchConfig, CAMConfig, CircuitConfig,
                            DeviceConfig)
    return CAMConfig(
        app=AppConfig(distance="l2", match_type="best", match_param=2,
                      data_bits=3),
        arch=ArchConfig(h_merge="adder", v_merge="comparator"),
        circuit=CircuitConfig(rows=8, cols=8, cell_type="mcam",
                              sensing="best"),
        device=DeviceConfig(device="fefet", variation=variation,
                            variation_std=0.8))


def test_cam_search_server_tail_padding_discards_padded_results():
    """A batch+1 submission leaves a 1-request tail step: the padded
    zero-queries ride the search but their results must be discarded, and
    every answer must equal the unpadded single-shot query bit-for-bit."""
    from repro.core import FunctionalSimulator
    from repro.runtime import CAMSearchServer

    sim = FunctionalSimulator(_cam_server_cfg())
    state = sim.write(jax.random.uniform(KEY, (30, 16)))
    batch = 4
    queries = np.asarray(jax.random.uniform(jax.random.PRNGKey(2),
                                            (batch + 1, 16)))
    srv = CAMSearchServer(sim, state, batch=batch)
    reqs = [srv.submit(q) for q in queries]
    done = srv.run()
    assert len(done) == batch + 1 and all(r.done for r in reqs)
    for q, r in zip(queries, reqs):
        idx, mask = sim.query(state, jnp.asarray(q))     # single, unpadded
        np.testing.assert_array_equal(r.indices, np.asarray(idx))
        np.testing.assert_array_equal(r.mask, np.asarray(mask))


def test_cam_search_server_empty_step_does_not_fold_key():
    """step() on an empty queue returns 0 WITHOUT consuming a per-step C2C
    key: the first real batch must still search with fold_in(key, 0)."""
    from repro.core import FunctionalSimulator
    from repro.runtime import CAMSearchServer

    sim = FunctionalSimulator(_cam_server_cfg("c2c"))
    state = sim.write(jax.random.uniform(KEY, (30, 16)))
    srv = CAMSearchServer(sim, state, batch=4)
    for _ in range(3):
        assert srv.step() == 0
    assert srv._steps == 0
    qs = np.asarray(jax.random.uniform(jax.random.PRNGKey(3), (4, 16)))
    for q in qs:
        srv.submit(q)
    assert srv.step() == 4
    idx, mask = sim.query(state, jnp.asarray(qs),
                          key=jax.random.fold_in(srv.key, 0))
    for i, r in enumerate(srv.finished):
        np.testing.assert_array_equal(r.indices, np.asarray(idx[i]))
        np.testing.assert_array_equal(r.mask, np.asarray(mask[i]))


def test_cam_search_server_c2c_keys_differ_across_steps():
    """Each served batch draws its cycle noise from fold_in(key, step):
    consecutive steps use different keys, and each step's answers are
    bit-identical to a direct query under that step's key."""
    from repro.core import FunctionalSimulator
    from repro.runtime import CAMSearchServer

    sim = FunctionalSimulator(_cam_server_cfg("c2c"))
    state = sim.write(jax.random.uniform(KEY, (30, 16)))
    batch = 4
    q = np.asarray(jax.random.uniform(jax.random.PRNGKey(4), (16,)))
    srv = CAMSearchServer(sim, state, batch=batch)
    for _ in range(2 * batch):          # the SAME query in both batches
        srv.submit(q)
    assert srv.step() == batch and srv.step() == batch
    k0 = jax.random.fold_in(srv.key, 0)
    k1 = jax.random.fold_in(srv.key, 1)
    assert not np.array_equal(np.asarray(k0), np.asarray(k1))
    qs = jnp.asarray(np.stack([q] * batch))
    for step, key in ((0, k0), (1, k1)):
        idx, mask = sim.query(state, qs, key=key)
        for i in range(batch):
            r = srv.finished[step * batch + i]
            np.testing.assert_array_equal(r.indices, np.asarray(idx[i]))
            np.testing.assert_array_equal(r.mask, np.asarray(mask[i]))


def test_cam_search_server_reads_serve_batch_from_config_and_facade():
    """batch=None: the server picks up config.sim.serve_batch, and accepts
    the CAMASim facade as its simulator."""
    from repro.core import CAMASim
    from repro.runtime import CAMSearchServer

    cfg = _cam_server_cfg().replace(sim=dict(serve_batch=4))
    sim = CAMASim(cfg)
    state = sim.write(jax.random.uniform(KEY, (30, 16)))
    srv = CAMSearchServer(sim, state)
    assert srv.batch == 4
    queries = np.asarray(jax.random.uniform(jax.random.PRNGKey(5), (9, 16)))
    for q in queries:
        srv.submit(q)
    assert srv.step() == 4                  # one serve_batch-sized step
    done = srv.run()
    assert len(done) == 9
    idx, mask = sim.query(state, jnp.asarray(queries))
    for i, r in enumerate(done):
        np.testing.assert_array_equal(r.indices, np.asarray(idx[i]))
        np.testing.assert_array_equal(r.mask, np.asarray(mask[i]))


def test_cam_search_server_autoscale_ladder_widths():
    """The padded width is the smallest power-of-two rung >= the step's
    requests, capped at batch; fixed-batch always pads to batch."""
    from repro.core import FunctionalSimulator
    from repro.runtime import CAMSearchServer

    sim = FunctionalSimulator(_cam_server_cfg())
    state = sim.write(jax.random.uniform(KEY, (30, 16)))
    auto = CAMSearchServer(sim, state, batch=32, autoscale=True)
    fixed = CAMSearchServer(sim, state, batch=32)
    for n, want in ((1, 1), (2, 2), (3, 4), (5, 8), (9, 16), (17, 32),
                    (32, 32)):
        assert auto._padded_width(n) == want, n
        assert fixed._padded_width(n) == 32, n


def test_cam_search_server_autoscale_parity_with_fixed_batch():
    """Same requests, same fold_in(key, step) schedule: the autoscaled
    server's answers are bit-exact vs fixed-batch serving (the ladder only
    changes the zero-padding width)."""
    from repro.core import FunctionalSimulator
    from repro.runtime import CAMSearchServer

    sim = FunctionalSimulator(_cam_server_cfg())
    state = sim.write(jax.random.uniform(KEY, (30, 16)))
    queries = np.asarray(jax.random.uniform(jax.random.PRNGKey(6),
                                            (11, 16)))
    key = jax.random.PRNGKey(9)
    fixed = CAMSearchServer(sim, state, batch=8, key=key)
    auto = CAMSearchServer(sim, state, batch=8, key=key, autoscale=True)
    for srv in (fixed, auto):
        for q in queries:
            srv.submit(q)
        srv.run()
    assert fixed._steps == auto._steps == 2   # same request grouping
    for rf, ra in zip(fixed.finished, auto.finished):
        assert rf.rid == ra.rid
        np.testing.assert_array_equal(rf.indices, ra.indices)
        np.testing.assert_array_equal(rf.mask, ra.mask)


def test_cam_search_server_autoscale_c2c_matches_direct_padded_query():
    """With C2C noise the per-cycle draw count is the padded width, so
    each autoscaled step must bit-match a direct query of that step's
    ladder width under the same fold_in(key, step) key."""
    from repro.core import FunctionalSimulator
    from repro.runtime import CAMSearchServer

    sim = FunctionalSimulator(_cam_server_cfg("c2c"))
    state = sim.write(jax.random.uniform(KEY, (30, 16)))
    queries = np.asarray(jax.random.uniform(jax.random.PRNGKey(7),
                                            (3, 16)))
    srv = CAMSearchServer(sim, state, batch=8, autoscale=True)
    for q in queries:
        srv.submit(q)
    assert srv.step() == 3                   # ladder width 4, one step
    padded = np.concatenate([queries, np.zeros((1, 16), np.float32)])
    idx, mask = sim.query(state, jnp.asarray(padded),
                          key=jax.random.fold_in(srv.key, 0))
    for i, r in enumerate(srv.finished):
        np.testing.assert_array_equal(r.indices, np.asarray(idx[i]))
        np.testing.assert_array_equal(r.mask, np.asarray(mask[i]))


def _cascade_cfg():
    from repro.core import CAMConfig
    return CAMConfig.from_dict(dict(
        app=dict(distance="l2", match_type="best", match_param=1,
                 data_bits=3),
        arch=dict(h_merge="adder", v_merge="comparator"),
        circuit=dict(rows=8, cols=8, cell_type="mcam", sensing="best"),
        device=dict(device="fefet"),
        sim=dict(prefilter="signature", top_p_banks=2)))


def test_cascade_pad_routing_regression():
    """THE serve-padding routing bug: `select_banks` min-reduces per-query
    margins over the batch axis, so an all-zero pad query used to vote for
    ITS best banks and evict the real query's — padded answers diverged
    from the unpadded ones.  `valid_count` must make them bit-identical,
    and on these seeds the unmasked padded query must still reproduce the
    divergence (else the regression test guards nothing)."""
    from repro.core import FunctionalSimulator

    sim = FunctionalSimulator(_cascade_cfg())
    state = sim.write(jax.random.uniform(jax.random.PRNGKey(0), (64, 8)))
    diverged = 0
    for qseed in (1000, 1001, 1005):
        q = jax.random.uniform(jax.random.PRNGKey(qseed), (1, 8))
        direct = sim.query(state, q)
        for width in (2, 4, 8):
            padded = jnp.concatenate(
                [q, jnp.zeros((width - 1, 8), q.dtype)])
            fixed = sim.query(state, padded, valid_count=1)
            np.testing.assert_array_equal(np.asarray(direct.indices[0]),
                                          np.asarray(fixed.indices[0]))
            np.testing.assert_array_equal(np.asarray(direct.mask[0]),
                                          np.asarray(fixed.mask[0]))
            buggy = sim.query(state, padded)       # no mask: pads vote
            if not np.array_equal(np.asarray(direct.indices[0]),
                                  np.asarray(buggy.indices[0])):
                diverged += 1
    assert diverged > 0      # the masked path is actually load-bearing


def test_cascade_served_answers_stable_across_pad_widths_and_depths():
    """Through the server: the same requests answer bit-identically no
    matter the serve batch, autoscale rung, or how many other requests
    share the queue — pad queries never steer the cascade's bank vote."""
    from repro.core import FunctionalSimulator
    from repro.runtime import CAMSearchServer

    sim = FunctionalSimulator(_cascade_cfg())
    state = sim.write(jax.random.uniform(jax.random.PRNGKey(0), (64, 8)))
    queries = np.asarray(jax.random.uniform(jax.random.PRNGKey(1000),
                                            (3, 8)))
    want = sim.query(state, jnp.asarray(queries), valid_count=3)
    for batch, autoscale in ((4, False), (8, False), (8, True), (16, True)):
        srv = CAMSearchServer(sim, state, batch=batch, autoscale=autoscale)
        for q in queries:
            srv.submit(q)
        done = srv.run()
        assert len(done) == 3
        for i, r in enumerate(done):
            np.testing.assert_array_equal(r.indices,
                                          np.asarray(want.indices[i]))
            np.testing.assert_array_equal(r.mask, np.asarray(want.mask[i]))


def test_cam_search_server_valid_count_noop_without_cascade():
    """valid_count is routing-only: with the cascade off it must not
    change full-batch answers (all-valid mask == no mask)."""
    from repro.core import FunctionalSimulator

    sim = FunctionalSimulator(_cam_server_cfg())
    state = sim.write(jax.random.uniform(KEY, (30, 16)))
    qs = jnp.asarray(np.asarray(
        jax.random.uniform(jax.random.PRNGKey(8), (4, 16))))
    a = sim.query(state, qs)
    b = sim.query(state, qs, valid_count=4)
    np.testing.assert_array_equal(np.asarray(a.indices),
                                  np.asarray(b.indices))
    np.testing.assert_array_equal(np.asarray(a.mask), np.asarray(b.mask))


def test_cam_search_server_rejects_malformed_requests_at_submit():
    """Malformed requests fail alone at the door — the queue they would
    have poisoned is untouched and keeps serving."""
    from repro.core import FunctionalSimulator
    from repro.runtime import CAMSearchServer

    sim = FunctionalSimulator(_cam_server_cfg())
    state = sim.write(jax.random.uniform(KEY, (30, 16)))
    srv = CAMSearchServer(sim, state, batch=4)
    good = srv.submit(np.zeros(16, np.float32))
    with pytest.raises(ValueError, match="shape"):
        srv.submit(np.zeros(9, np.float32))          # wrong width
    with pytest.raises(ValueError, match="numeric"):
        srv.submit(np.array(["a"] * 16))             # wrong dtype
    with pytest.raises(ValueError, match="width"):
        srv.submit_insert(np.zeros((2, 9), np.float32))
    with pytest.raises(ValueError, match="numeric"):
        srv.submit_insert(np.array([["a"] * 16]))
    with pytest.raises(ValueError, match="ids but"):
        srv.submit_update([1, 2], np.zeros((1, 16), np.float32))
    assert [r.rid for r in srv.queue] == [good.rid]
    assert srv.step() == 1 and good.done


def test_cam_search_server_step_failure_restores_queue():
    """A failing engine call must not lose requests: step() restores its
    popped batch to the queue front and re-raises; the retry then serves
    the SAME requests under the SAME fold_in(key, step) key."""
    from repro.core import FunctionalSimulator
    from repro.runtime import CAMSearchServer

    sim = FunctionalSimulator(_cam_server_cfg())
    state = sim.write(jax.random.uniform(KEY, (30, 16)))
    srv = CAMSearchServer(sim, state, batch=4)
    queries = np.asarray(jax.random.uniform(jax.random.PRNGKey(11),
                                            (3, 16)))
    reqs = [srv.submit(q) for q in queries]
    real_query = sim.query

    def boom(*a, **kw):
        raise RuntimeError("injected engine fault")

    sim.query = boom
    try:
        with pytest.raises(RuntimeError, match="injected"):
            srv.step()
    finally:
        sim.query = real_query
    assert [r.rid for r in srv.queue] == [r.rid for r in reqs]
    assert srv._steps == 0                   # key schedule untouched
    assert srv.step() == 3
    idx, mask = sim.query(state, jnp.asarray(queries))
    for i, r in enumerate(reqs):
        np.testing.assert_array_equal(r.indices, np.asarray(idx[i]))
        np.testing.assert_array_equal(r.mask, np.asarray(mask[i]))
    # mutation-unit failure restores too
    bad = srv.submit_delete([10**6])         # out-of-range id
    with pytest.raises(ValueError, match=r"ids must be in"):
        srv.step()
    assert srv.queue and srv.queue[0].rid == bad.rid


def test_cam_search_server_queue_full_backpressure():
    from repro.core import CAMASim
    from repro.runtime import CAMSearchServer, QueueFull

    cfg = _cam_server_cfg().replace(sim=dict(serve_queue=2))
    sim = CAMASim(cfg)
    state = sim.write(jax.random.uniform(KEY, (30, 16)))
    srv = CAMSearchServer(sim, state, batch=4)   # max_queue from config
    assert srv.max_queue == 2
    srv.submit(np.zeros(16, np.float32))
    srv.submit(np.zeros(16, np.float32))
    with pytest.raises(QueueFull):
        srv.submit(np.zeros(16, np.float32))
    srv.step()                                   # drains the queue
    srv.submit(np.zeros(16, np.float32))         # admits again
    # explicit max_queue overrides the config default
    assert CAMSearchServer(sim, state, batch=4, max_queue=7).max_queue == 7


def test_cam_search_server_mutations_interleave_deterministically():
    """insert → search → delete → search through the serve loop: answers
    reflect submission order, the final state is bit-identical to direct
    engine mutations under the server's mutation key lane, and an
    identical server replays the identical trace."""
    from repro.core import FunctionalSimulator
    from repro.runtime import CAMSearchServer

    cfg = _cam_server_cfg("both").replace(
        sim=dict(capacity=48, d2d_fold="row"),
        device=dict(variation_std=0.05))
    sim = FunctionalSimulator(cfg)
    stored = jax.random.uniform(KEY, (30, 16))
    stored = stored.at[0].set(0.0).at[1].set(1.0)
    extra = np.asarray(jax.random.uniform(jax.random.PRNGKey(12), (4, 16)))
    state = sim.write(stored, KEY)

    def drive(srv):
        ins = srv.submit_insert(extra)
        hits = [srv.submit(row) for row in extra]    # see the new rows
        dels = srv.submit_delete([3, 4])
        miss = srv.submit(np.asarray(stored[3]))     # deleted row's data
        srv.run()
        return ins, hits, dels, miss

    srv = CAMSearchServer(sim, state, batch=4, key=jax.random.PRNGKey(9))
    ins, hits, dels, miss = drive(srv)
    assert ins.done and dels.done
    np.testing.assert_array_equal(ins.ids, np.arange(30, 34))
    for i, r in enumerate(hits):                 # inserted rows match
        assert r.indices[0] == ins.ids[i]
    assert miss.indices[0] not in (3, 4)         # deleted rows never match
    # server state == direct mutations under the same mutation key lane
    mk = jax.random.fold_in(srv._mut_key, 0)
    direct, _ = sim.insert(state, jnp.asarray(extra), key=mk)
    direct = sim.delete(direct, [3, 4])
    np.testing.assert_array_equal(np.asarray(srv.state.grid),
                                  np.asarray(direct.grid))
    np.testing.assert_array_equal(np.asarray(srv.state.row_valid),
                                  np.asarray(direct.row_valid))
    # identical server → identical trace
    srv2 = CAMSearchServer(sim, sim.write(stored, KEY), batch=4,
                           key=jax.random.PRNGKey(9))
    drive(srv2)
    assert len(srv.finished) == len(srv2.finished)
    for a, b in zip(srv.finished, srv2.finished):
        assert a.rid == b.rid and a.slo == b.slo
        if hasattr(a, "query"):                  # search requests
            np.testing.assert_array_equal(a.indices, b.indices)
            np.testing.assert_array_equal(a.mask, b.mask)


def test_cam_search_server_latency_stats_by_slo():
    from repro.core import FunctionalSimulator
    from repro.runtime import CAMSearchServer

    sim = FunctionalSimulator(_cam_server_cfg())
    state = sim.write(jax.random.uniform(KEY, (30, 16)))
    srv = CAMSearchServer(sim, state, batch=4)
    for i in range(5):
        srv.submit(np.zeros(16, np.float32),
                   slo="interactive" if i % 2 else "batch")
    srv.submit_insert(np.ones((1, 16), np.float32))
    srv.run()
    stats = srv.latency_stats()
    assert set(stats) == {"interactive", "batch", "mutation"}
    assert stats["interactive"]["n"] == 2 and stats["batch"]["n"] == 3
    for s in stats.values():
        assert 0 <= s["p50_us"] <= s["p99_us"]
        # the queueing part (submit to start) is a part of the whole
        assert 0 <= s["queue_p50_us"] <= s["queue_p99_us"]
        assert s["queue_p50_us"] <= s["p50_us"]
        assert s["queue_p99_us"] <= s["p99_us"]
    # the queueing percentiles read the submit-to-start times
    batch = [r for r in srv.finished if r.slo == "batch"]
    waits = [(r.t_start - r.t_submit) * 1e6 for r in batch]
    assert stats["batch"]["queue_p99_us"] == pytest.approx(
        float(np.percentile(waits, 99)))


def test_cam_search_server_counters_count_steps_requests_and_bytes():
    from repro.core import FunctionalSimulator
    from repro.runtime import CAMSearchServer

    sim = FunctionalSimulator(_cam_server_cfg())
    state = sim.write(jax.random.uniform(KEY, (30, 16)))
    srv = CAMSearchServer(sim, state, batch=4)
    assert srv.counters == {"steps": 0, "searches": 0, "fetch_bytes": 0}
    queries = np.asarray(jax.random.uniform(jax.random.PRNGKey(3),
                                            (12, 16)))
    reqs = [srv.submit(q) for q in queries]
    srv.run()
    handed = sum(r.indices.nbytes + r.mask.nbytes for r in reqs)
    assert srv.counters == {"steps": 3, "searches": 12,
                            "fetch_bytes": handed}
    # a part-filled step fetches its whole padded batch
    srv.submit(queries[0])
    srv.step()
    k, padded_K = reqs[0].indices.shape[0], reqs[0].mask.shape[0]
    assert srv.counters["steps"] == 4 and srv.counters["searches"] == 13
    assert srv.counters["fetch_bytes"] == handed + 4 * (k + padded_K) * 4
    srv.step()                                   # empty queue: no step
    assert srv.counters["steps"] == 4


def test_cam_search_server_requests_carry_their_step_and_start():
    from repro.core import FunctionalSimulator
    from repro.runtime import CAMSearchServer

    cfg = _cam_server_cfg().replace(sim=dict(capacity=48))
    sim = FunctionalSimulator(cfg)
    state = sim.write(jax.random.uniform(KEY, (30, 16)))
    srv = CAMSearchServer(sim, state, batch=4)
    searches = [srv.submit(np.full(16, i / 10, np.float32))
                for i in range(6)]
    ins = srv.submit_insert(np.ones((2, 16), np.float32))
    late = srv.submit(np.zeros(16, np.float32))
    srv.run()
    assert [r.step for r in searches] == [0, 0, 0, 0, 1, 1]
    assert ins.step == 2 and late.step == 2     # the run rides step 2
    for r in searches + [ins, late]:
        assert r.t_submit <= r.t_start <= r.t_done
    assert searches[0].t_start == searches[3].t_start
    assert searches[3].t_done <= searches[4].t_start
    assert ins.t_start <= late.t_start


def test_cam_search_server_step_spans_nest_in_order(tmp_path):
    """A profiled step holds the serve engine's spans, read back by the
    benchmark's trace reader: dispatch, wait and fetch inside the step,
    one after another."""
    import glob

    from bench import program_trace
    from bench import trace as tr
    from repro.core import FunctionalSimulator
    from repro.runtime import CAMSearchServer

    sim = FunctionalSimulator(_cam_server_cfg())
    state = sim.write(jax.random.uniform(KEY, (30, 16)))
    srv = CAMSearchServer(sim, state, batch=4)
    for _ in range(2):                           # compile outside the trace
        srv.submit(np.zeros(16, np.float32))
    srv.run()
    for _ in range(4):
        srv.submit(np.zeros(16, np.float32))
    jax.profiler.start_trace(str(tmp_path),
                             profiler_options=tr.capture_options())
    before = srv.counters["fetch_bytes"]
    srv.step()
    jax.profiler.stop_trace()
    t = tr.load(str(tmp_path))
    step = t.span("cam.serve.step")
    assert step is not None
    inner = [t.span(n) for n in ("cam.serve.dispatch", "cam.serve.wait",
                                 "cam.serve.fetch")]
    assert all(e is not None for e in inner)
    assert step.start <= inner[0].start
    for a, b in zip(inner, inner[1:]):
        assert a.end <= b.start
    assert inner[-1].end <= step.end
    assert all(e.depth > step.depth for e in inner)
    assert [r.step for r in srv.finished[-4:]] == [1, 1, 1, 1]
    # the step span names its step; the fetch span, the bytes it fetched
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    stats = {s.name: s.stats for s in program_trace.host_spans(path)}
    assert stats["cam.serve.step"]["step_num"] == 1
    assert stats["cam.serve.fetch"] == {
        "fetch_bytes": srv.counters["fetch_bytes"] - before}


def _scopes(sim, state, queries):
    import re
    text = type(sim)._query_jit.lower(
        sim, state, queries, jax.random.PRNGKey(1), None).compile().as_text()
    # a scope under a transform reads "vmap(cam.merge)"
    return {scope for name in re.findall(r'op_name="([^"]*)"', text)
            for scope in re.findall(r"(?:^|[/(])(cam\.[a-z]+)", name)}


@pytest.mark.parametrize("variation", ["none", "c2c"])
def test_search_program_carries_its_named_scopes(variation):
    from repro.core import FunctionalSimulator

    sim = FunctionalSimulator(_cam_server_cfg(variation))
    state = sim.write(jax.random.uniform(KEY, (30, 16)), KEY)
    assert state.perm is None
    assert _scopes(sim, state, jnp.zeros((4, 16))) == {
        "cam.quantize", "cam.search", "cam.merge"}


def test_search_program_scopes_the_back_map_where_rows_are_placed():
    from repro.core import FunctionalSimulator

    cfg = _cam_server_cfg().replace(sim=dict(prefilter="ivf"))
    sim = FunctionalSimulator(cfg)
    state = sim.write(jax.random.uniform(KEY, (30, 16)), KEY)
    assert state.perm is not None
    assert _scopes(sim, state, jnp.zeros((4, 16))) == {
        "cam.quantize", "cam.search", "cam.merge", "cam.backmap"}


# ---------------------------------------------------------------------------
# sharding resolver
# ---------------------------------------------------------------------------
def _mesh_16x16_abstract():
    # AbstractMesh-like resolution check without devices: use a tiny mesh
    # and a fake big one via spec_for's pure math (mesh only provides
    # axis names and sizes, so we use jax.sharding.AbstractMesh).
    from jax.sharding import AbstractMesh
    return AbstractMesh((16, 16), ("data", "model"))


def test_resolver_divisibility_fallback():
    rules = ShardingRules()
    mesh = _mesh_16x16_abstract()
    # 12 heads on model=16: must NOT shard
    spec = rules.spec_for((1536, 12, 128),
                          ("embed", "heads", "head_dim"), mesh)
    assert spec == jax.sharding.PartitionSpec()
    # d_ff 8960 shards fine
    spec = rules.spec_for((1536, 8960), ("embed", "mlp"), mesh)
    assert spec == jax.sharding.PartitionSpec(None, "model")


def test_resolver_no_double_axis_use():
    rules = ShardingRules()
    mesh = _mesh_16x16_abstract()
    # both dims want 'model': only one (higher priority) gets it
    spec = rules.spec_for((4096, 4096), ("mlp", "vocab"), mesh)
    got = [s for s in spec if s is not None]
    assert got.count("model") <= 1


def test_resolver_cam_rules():
    """cam_bank/cam_query resolve on a CAM mesh and stay silent on the
    LM meshes (no 'bank'/'query' axes there)."""
    from jax.sharding import AbstractMesh
    rules = ShardingRules()
    cam_mesh = AbstractMesh((4, 2), ("bank", "query"))
    spec = rules.spec_for((8, 2, 16, 16),
                          ("cam_bank", None, "cam_row", "cam_col"),
                          cam_mesh)
    assert spec == jax.sharding.PartitionSpec("bank")
    qspec = rules.spec_for((6, 2, 16), ("cam_query", None, None), cam_mesh)
    assert qspec == jax.sharding.PartitionSpec("query")
    # nv=3 does not divide bank=4: replicated, never a crash
    assert rules.spec_for((3, 2, 16, 16),
                          ("cam_bank", None, None, None),
                          cam_mesh) == jax.sharding.PartitionSpec()
    # LM mesh: cam axes silently replicate
    lm = _mesh_16x16_abstract()
    assert rules.spec_for((8, 2, 16, 16),
                          ("cam_bank", None, None, None),
                          lm) == jax.sharding.PartitionSpec()


def test_resolver_kv_seq_takes_data_when_batch_cannot():
    rules = ShardingRules()
    mesh = _mesh_16x16_abstract()
    # batch=1 long-context: kv_seq gets model AND data
    spec = rules.spec_for((36, 1, 524288, 8, 128),
                          ("layers", "batch", "kv_seq", "kv_heads",
                           "head_dim"), mesh)
    flat = []
    for s in spec:
        if isinstance(s, tuple):
            flat += list(s)
        elif s:
            flat.append(s)
    assert "model" in flat and "data" in flat


def test_resolver_fsdp_on_params():
    rules = ShardingRules()
    mesh = _mesh_16x16_abstract()
    spec = rules.spec_for((4096, 14336), ("embed", "mlp"), mesh,
                          fsdp=True)
    flat = []
    for s in spec:
        if isinstance(s, tuple):
            flat += list(s)
        elif s:
            flat.append(s)
    assert "data" in flat and "model" in flat


# ---------------------------------------------------------------------------
# data pipeline
# ---------------------------------------------------------------------------
def test_data_determinism_and_range():
    d = SyntheticLM(vocab_size=1000, seq_len=16, global_batch=4, seed=3)
    b1, b2 = d.batch(7), d.batch(7)
    np.testing.assert_array_equal(np.asarray(b1["tokens"]),
                                  np.asarray(b2["tokens"]))
    assert (np.asarray(b1["tokens"]) < 1000).all()
    b3 = d.batch(8)
    assert np.abs(np.asarray(b3["tokens"]) -
                  np.asarray(b1["tokens"])).max() > 0
    # restart-from-state reproduces the stream
    d2 = SyntheticLM.from_state(d.state(7))
    np.testing.assert_array_equal(np.asarray(d2.batch(7)["tokens"]),
                                  np.asarray(b1["tokens"]))


def test_train_microbatch_equivalence():
    """Grad accumulation over k microbatches == one big batch (same data)."""
    cfg = get_config("qwen2-1.5b").reduced().replace(dtype="float32")
    import dataclasses
    import repro.models.layers as L
    from repro import models
    opt = AdamW(lr=constant(1e-2), clip_norm=None)
    spec = models.model_specs(cfg)
    spec = L.tree_map_specs(
        lambda p: dataclasses.replace(p, dtype=jnp.float32), spec)
    params = L.init_params(KEY, spec)
    from repro.runtime.train_loop import TrainState
    state = TrainState(step=jnp.zeros((), jnp.int32), params=params,
                       opt=opt.init(params))
    data = SyntheticLM(cfg.vocab_size, 16, 8, seed=0)
    batch = data.batch(0)
    s1, m1 = jax.jit(make_train_step(cfg, opt))(state, batch)
    s2, m2 = jax.jit(make_train_step(cfg, opt, microbatch=4))(state, batch)
    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]),
                               rtol=1e-4)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=5e-5),
        s1.params, s2.params)
