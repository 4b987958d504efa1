"""Tests for the slab gradient path (``repro.core.slab``): codec
round-trip properties, numerical parity of the slab aggregation against
the legacy pytree fold (bitwise for the sync mean, allclose for weighted
flushes), the donation contract (published params survive later donated
flushes; snapshots stay valid while flushes continue), and the
one-flush-executable guarantee for any fleet size."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.slab import SlabAggregator, SlabBuffer, slab_codec
from repro.cluster.server import ParameterServer
from repro.cluster.transport import GradientMsg, ParamsMsg
from repro.core.schedule import constant_schedule, step_schedule
from repro.kernels.hybrid_aggregate import TILE_P
from repro.optim import SlabOptimizer


def _tree(seed: int, scale: float = 1.0, shapes=None):
    shapes = shapes or {"w1": (20, 64), "b1": (64,), "w2": (64, 10)}
    ks = jax.random.split(jax.random.PRNGKey(seed), len(shapes))
    return {name: scale * jax.random.normal(k, s)
            for k, (name, s) in zip(ks, sorted(shapes.items()))}


@jax.jit
def _legacy_agg_apply_jit(params, grads, weights, scale):
    wsum = jnp.sum(weights)

    def comb(p, *leaves):
        s = weights[0] * leaves[0]
        for w, leaf in zip(weights[1:], leaves[1:]):
            s = s + w * leaf
        return p - scale * (s / wsum)

    return jax.tree.map(comb, params, *grads)


def legacy_agg_apply(params, grads, weights, scale):
    """The pre-slab server's fused aggregate+apply, verbatim: one
    *jitted* executable per buffer size K, folding the K gradient
    pytrees leaf by leaf, normalized by Σw.  (Jitted like the original —
    eager execution skips XLA's FMA contraction and drifts by 1 ulp.)
    The slab executable must reproduce it bitwise for uniform weights."""
    return _legacy_agg_apply_jit(params, tuple(grads),
                                 jnp.asarray(weights, jnp.float32),
                                 jnp.float32(scale))


# ----------------------------------------------------------------- codec

@settings(max_examples=25, deadline=None)
@given(n_leaves=st.integers(1, 4), seed=st.integers(0, 2 ** 16),
       dim=st.sampled_from([1, 3, 17, 128, 300]),
       ranks=st.sampled_from([(1,), (2,), (1, 2), (3, 1)]))
def test_codec_round_trip_property(n_leaves, seed, dim, ranks):
    """Property: decode(encode(tree)) is bitwise identical for any tree
    of floating leaves, and the slab is tile-aligned with zero padding."""
    key = jax.random.PRNGKey(seed)
    tree = {}
    for i in range(n_leaves):
        key, k = jax.random.split(key)
        shape = tuple(dim + i for _ in range(ranks[i % len(ranks)]))
        tree[f"leaf{i}"] = jax.random.normal(k, shape)
    codec = slab_codec(tree)
    slab = codec.encode(tree)
    assert slab.shape == (codec.padded_size,) and slab.dtype == jnp.float32
    assert codec.padded_size % TILE_P == 0
    assert codec.size == sum(np.prod(s) for s in codec.shapes)
    np.testing.assert_array_equal(
        np.asarray(slab[codec.size:]), 0.0)        # padding is zeros
    back = codec.decode(slab)
    for name in tree:
        got, want = np.asarray(back[name]), np.asarray(tree[name])
        assert got.dtype == want.dtype and got.shape == want.shape
        np.testing.assert_array_equal(got, want)


def test_codec_bf16_round_trip_exact():
    """bf16 leaves widen to f32 on the slab and narrow back exactly."""
    tree = {"w": jax.random.normal(jax.random.PRNGKey(0), (64, 8)
                                   ).astype(jnp.bfloat16)}
    codec = slab_codec(tree)
    back = codec.decode(codec.encode(tree))
    assert back["w"].dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(back["w"], np.float32),
                                  np.asarray(tree["w"], np.float32))


def test_codec_cached_per_structure():
    """Same structure -> same codec object (and compiled executables);
    different shapes -> a different codec."""
    assert slab_codec(_tree(0)) is slab_codec(_tree(1))
    other = slab_codec({"w": jnp.zeros((4, 4))})
    assert other is not slab_codec(_tree(0))


def test_codec_rejects_integer_leaves():
    with pytest.raises(TypeError, match="floating"):
        slab_codec({"ids": jnp.zeros((3,), jnp.int32)})


def test_codec_layout_offsets():
    """Leaves occupy [offset, offset+size) in flatten order."""
    tree = _tree(3)
    codec = slab_codec(tree)
    slab = np.asarray(codec.encode(tree))
    leaves = jax.tree_util.tree_leaves(tree)
    for leaf, off, n in zip(leaves, codec.offsets, codec.sizes):
        np.testing.assert_array_equal(slab[off:off + n],
                                      np.asarray(leaf).ravel())


# ------------------------------------------------ aggregator vs legacy

def test_slab_flush_bitwise_equals_legacy_sync_fold():
    """Uniform weights (the sync round mean): the slab executable's fold
    must be bitwise identical to the legacy per-leaf fold."""
    params, grads = _tree(0), [_tree(i + 1, 0.01) for i in range(3)]
    codec = slab_codec(params)
    agg = SlabAggregator(codec, params, k_max=5)
    for i, g in enumerate(grads):
        agg.stage(codec.encode(g), i)
    pub = agg.flush_apply(np.ones(3), 0.05)
    want = legacy_agg_apply(params, tuple(grads), np.ones(3), 0.05)
    got = codec.decode(pub)
    for name in params:
        np.testing.assert_array_equal(np.asarray(got[name]),
                                      np.asarray(want[name]), err_msg=name)


@pytest.mark.parametrize("weights", [
    np.array([1.0, 0.9, 0.81, 0.729]),     # staleness decay 0.9
    np.array([0.3, 1.0, 0.3, 0.7]),
])
def test_slab_flush_weighted_allclose_legacy(weights):
    params, grads = _tree(0), [_tree(i + 1, 0.01) for i in range(4)]
    codec = slab_codec(params)
    agg = SlabAggregator(codec, params, k_max=4)
    for i, g in enumerate(grads):
        agg.stage(codec.encode(g), i)
    got = codec.decode(agg.flush_apply(weights, 0.04))
    want = legacy_agg_apply(params, tuple(grads), weights, 0.04)
    for name in params:
        np.testing.assert_allclose(np.asarray(got[name]),
                                   np.asarray(want[name]),
                                   rtol=1e-6, atol=1e-7, err_msg=name)


def test_slab_flush_pallas_interpret_matches_jnp():
    """The Pallas kernel (interpret mode on CPU) and the jnp fallback
    compute the same flush — the TPU/CPU backend matrix is consistent."""
    params, grads = _tree(0), [_tree(i + 1, 0.01) for i in range(3)]
    codec = slab_codec(params)
    outs = []
    for use_pallas in (False, True):
        agg = SlabAggregator(codec, params, k_max=4,
                             use_pallas=use_pallas, interpret=use_pallas)
        for i, g in enumerate(grads):
            agg.stage(codec.encode(g), i)
        outs.append(np.asarray(
            agg.flush_apply(np.array([1.0, 0.9, 0.81]), 0.03)))
    np.testing.assert_allclose(outs[0], outs[1], rtol=1e-6, atol=1e-7)


# ------------------------------------------------- staging by reference

@pytest.mark.parametrize("slab_dtype,source,held", [
    ("f32", "device f32", True),
    ("bf16", "device bf16", True),
    ("bf16", "host bf16", False),
    ("bf16", "host f32", False),
    ("bf16", "device f32", False),
])
def test_stage_holds_device_rows_and_copies_the_rest(slab_dtype, source,
                                                      held, caplog):
    """A device slab already in the staging dtype is held as the very
    array: nothing is compiled, run or copied.  A host row (a socket
    transport's) or a row in another dtype takes one transfer or cast
    into a fresh device row of the staging dtype."""
    params = _tree(0)
    codec = slab_codec(params, slab_dtype)
    agg = SlabAggregator(codec, params, k_max=2)
    where, dtype = source.split()
    row = slab_codec(params, dtype).encode(_tree(1, 0.01))
    jax.block_until_ready(row)
    if where == "host":
        row = np.asarray(row)
    caplog.clear()
    with jax.log_compiles(True):
        assert agg.stage(row, 1) is held
    staged, = agg._rows[1]
    assert agg._rows[0] is None
    if held:
        assert staged is row
        assert not [r for r in caplog.records
                    if "Compiling" in r.getMessage()]
        return
    assert isinstance(staged, jax.Array) and staged is not row
    assert staged.dtype == codec.slab_dtype
    np.testing.assert_array_equal(
        np.asarray(staged, np.float32),
        np.asarray(jnp.asarray(row).astype(codec.slab_dtype), np.float32))


@pytest.mark.parametrize("drop", ["flush", "discard"])
def test_aggregator_releases_rows_it_no_longer_needs(drop):
    """Rows a flush consumed, or a restore discarded, are no longer
    referenced by the aggregator: their device memory goes when the
    caller lets go of them."""
    import gc
    import weakref
    params = _tree(0)
    codec = slab_codec(params)
    agg = SlabAggregator(codec, params, k_max=3)
    buf = SlabBuffer(agg)
    rows = [codec.encode(_tree(i + 1, 0.01)) for i in range(2)]
    for r in rows:
        assert buf.add(r, 0)
    refs = [weakref.ref(r) for r in rows]
    if drop == "flush":
        jax.block_until_ready(agg.flush_apply(buf.weights(0), 0.05))
        buf.clear()
    else:
        buf.discard()
    assert agg._rows == [None] * 3 and len(buf) == 0
    del rows, r
    gc.collect()
    assert all(ref() is None for ref in refs)


def test_grow_keeps_staged_rows():
    """Growing the slots mid-buffer keeps the rows already staged, as
    they were, and the grown aggregator's flush is bitwise that of one
    built at the larger K_max."""
    params = _tree(0)
    codec = slab_codec(params)
    rows = [codec.encode(_tree(i + 1, 0.01)) for i in range(3)]
    agg = SlabAggregator(codec, params, k_max=2)
    agg.stage(rows[0], 0)
    agg.stage(rows[1], 1)
    agg.grow(4)
    assert agg.k_max == 4 and len(agg._rows) == 4
    assert agg._rows[0][0] is rows[0] and agg._rows[1][0] is rows[1]
    agg.stage(rows[2], 2)
    got = agg.flush_apply(np.array([1.0, 0.9, 0.8]), 0.05)
    fixed = SlabAggregator(codec, params, k_max=4)
    for i, r in enumerate(rows):
        fixed.stage(r, i)
    want = fixed.flush_apply(np.array([1.0, 0.9, 0.8]), 0.05)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert agg.flush_cache_size() == 1


def test_slab_buffer_staleness_weights_clamped():
    """decay^(now - v) with the exponent clamped at 0: a gradient tagged
    with a *future* version (post-restore) is not up-weighted."""
    params = _tree(0)
    agg = SlabAggregator(slab_codec(params), params, k_max=3)
    buf = SlabBuffer(agg, staleness_decay=0.5)
    slab = agg.codec.encode(_tree(1, 0.01))
    for v in (4, 6, 9):                    # staleness 2, 0, -3 at now=6
        buf.add(slab, v)
    np.testing.assert_allclose(buf.weights(6), [0.25, 1.0, 1.0])
    buf.clear()
    assert len(buf) == 0


# ------------------------------------------------------ donation contract

def test_published_params_survive_later_donated_flushes():
    """The flush executable's second output (the published params) must
    never alias the donated buffer: copies handed out at version v stay
    bitwise intact while the server keeps flushing."""
    params = _tree(0)
    codec = slab_codec(params)
    agg = SlabAggregator(codec, params, k_max=2)
    g = codec.encode(_tree(1, 0.01))
    agg.stage(g, 0)
    pub_v1 = agg.flush_apply(np.ones(1), 0.01)
    held = np.asarray(pub_v1).copy()
    for _ in range(5):                      # donations keep recycling
        agg.stage(g, 0)                     # the private buffers
        agg.flush_apply(np.ones(1), 0.01)
    np.testing.assert_array_equal(np.asarray(pub_v1), held)
    # and the params did actually move on
    assert not np.array_equal(np.asarray(agg.params_slab), held)


class _CellTransport:
    """Minimal transport stub: remembers the last published params."""

    def __init__(self):
        self.published = []

    def publish_params(self, msg: ParamsMsg):
        self.published.append(msg)

    def send_gradient(self, msg, timeout=None):   # pragma: no cover
        return True

    def recv_gradient(self, timeout=None):        # pragma: no cover
        return None

    def pending_gradients(self):                  # pragma: no cover
        return 0


def _server(mode="hybrid", num_workers=3, schedule=None, **kw):
    params = _tree(0)
    if mode in ("async", "hybrid") and schedule is None:
        schedule = constant_schedule(num_workers,
                                     1 if mode == "async" else 2)
    return params, ParameterServer(
        params, lr=0.05, mode=mode, transport=_CellTransport(),
        num_workers=num_workers, schedule=schedule, **kw)


def test_snapshot_survives_continued_flushes():
    """Regression for the checkpoint-under-donation hazard: a snapshot
    taken mid-run must be a copy — its values stay bitwise intact while
    later flushes keep donating (and therefore recycling) the server's
    params buffers."""
    params, server = _server(mode="async", num_workers=2)
    codec = server.codec
    grads = [codec.encode(_tree(i + 1, 0.01)) for i in range(4)]
    for i in range(3):
        server.ingest(GradientMsg(0, grads[i], server.version, i))
    version, snap, applied = server.snapshot()
    held = {k: np.asarray(v).copy() for k, v in snap.items()}
    for i in range(40):                 # checkpoint-while-training
        server.ingest(GradientMsg(0, grads[i % 4], server.version, i))
    for k in held:                      # the snapshot did not move
        np.testing.assert_array_equal(np.asarray(snap[k]), held[k])
    # while the live params did
    _, now, _ = server.snapshot()
    assert any(not np.array_equal(held[k], np.asarray(now[k]))
               for k in held)
    assert version == 3 and applied == 3


# ------------------------------------------------- server parity / probe

def _replay_legacy(params, msgs, mode, schedule, lr, flush_mode="sum",
                   staleness_decay=1.0, num_workers=3):
    """Replay an ingest sequence through the pre-slab server semantics
    (pytree buffers + legacy_agg_apply) and return the final params."""
    version, buffer, round_ = 0, [], {}
    p = params
    for msg in msgs:
        if mode == "sync":
            if msg.version != version:
                continue
            round_[msg.worker_id] = msg.grad
            if set(round_) >= set(range(num_workers)):
                wids = sorted(round_)
                grads = [round_[w] for w in wids]
                round_ = {}
                p = legacy_agg_apply(p, tuple(grads),
                                     np.ones(len(grads)), lr)
                version += 1
        else:
            buffer.append((msg.grad, msg.version))
            if len(buffer) >= schedule(version):
                grads = [g for g, _ in buffer]
                stale = np.maximum(0.0, version - np.asarray(
                    [v for _, v in buffer], np.float64))
                weights = staleness_decay ** stale
                k = len(buffer)
                buffer = []
                scale = lr * k if flush_mode == "sum" else lr
                p = legacy_agg_apply(p, tuple(grads), weights, scale)
                version += 1
    return p, version


@pytest.mark.parametrize("mode,flush_mode,decay", [
    ("sync", "sum", 1.0),
    ("async", "sum", 1.0),
    ("hybrid", "sum", 1.0),
    ("hybrid", "mean", 1.0),
    ("hybrid", "sum", 0.9),
    ("hybrid", "mean", 0.9),
])
def test_server_slab_path_matches_legacy_pytree_path(mode, flush_mode,
                                                     decay):
    """Numerical parity of the live slab server against the pre-slab
    pytree path, on an identical deterministic ingest sequence: bitwise
    for the sync round mean, allclose <= 1e-6 for weighted flushes."""
    num_workers = 3
    schedule = None
    if mode == "hybrid":
        schedule = step_schedule(num_workers, 2)   # K anneals 1 -> 3
    elif mode == "async":
        schedule = constant_schedule(num_workers, 1)
    params, server = _server(mode=mode, num_workers=num_workers,
                             schedule=schedule, flush_mode=flush_mode,
                             staleness_decay=decay)
    for w in range(num_workers):
        server.register(w)
    codec = server.codec
    grad_trees = [_tree(100 + i, 0.01) for i in range(12)]

    # deterministic ingest: round-robin workers, each reading the
    # then-current version (so hybrid/async staleness is exercised but
    # reproducible)
    slab_msgs, tree_msgs = [], []
    for i, g in enumerate(grad_trees):
        wid = i % num_workers
        v = server.version
        msg = GradientMsg(wid, codec.encode(g), v, i)
        server.ingest(msg)
        tree_msgs.append(GradientMsg(wid, g, v, i))
        slab_msgs.append(msg)

    want, want_version = _replay_legacy(
        params, tree_msgs, mode, schedule, server.lr,
        flush_mode=flush_mode, staleness_decay=decay,
        num_workers=num_workers)
    assert server.version == want_version > 0
    _, got, _ = server.snapshot()
    for name in params:
        g, w = np.asarray(got[name]), np.asarray(want[name])
        if mode == "sync":
            np.testing.assert_array_equal(g, w, err_msg=name)  # bitwise
        else:
            np.testing.assert_allclose(g, w, rtol=1e-6, atol=1e-7,
                                       err_msg=name)


def test_restore_wipes_nonfinite_staged_gradients():
    """Regression: diverged (inf/nan) gradients sitting in the buffer
    when a restore discards them must not poison later flushes — zero
    masking alone is not enough (0 · inf = nan), so discard wipes.
    The restore rolls K(t) back to 1, so the discarded rows would be
    masked (never overwritten) by the next flush."""
    num_workers = 3
    schedule = step_schedule(num_workers, 1)       # K(v) = 1 + v
    params, server = _server(mode="hybrid", num_workers=num_workers,
                             schedule=schedule)
    codec = server.codec
    g = _tree(2, 0.01)
    for i in range(3):     # advance to version 2 (flushes at K=1, K=2)
        server.ingest(GradientMsg(i, codec.encode(g), server.version, i))
    assert server.version == 2 and len(server.buffer) == 0
    # two diverged gradients buffer at rows 0 and 1, awaiting K=3
    bad = jax.tree.map(lambda x: jnp.full_like(x, jnp.inf), _tree(1))
    for i in range(2):
        server.ingest(GradientMsg(i, codec.encode(bad),
                                  server.version, 3 + i))
    assert len(server.buffer) == 2
    server.restore(params, step=0)          # discards them; K back to 1
    assert server.dropped == 2
    # the next flush stages only row 0 — row 1 (the inf) is masked,
    # so without the wipe it would turn the params to NaN
    server.ingest(GradientMsg(0, codec.encode(g), server.version, 5))
    _, got, _ = server.snapshot()
    want = legacy_agg_apply(params, (g,), np.ones(1), server.lr)
    for name in params:
        assert np.isfinite(np.asarray(got[name])).all(), name
        np.testing.assert_array_equal(np.asarray(got[name]),
                                      np.asarray(want[name]), err_msg=name)


def test_hybrid_schedule_larger_than_fleet_does_not_overflow_staging():
    """Regression: a K(t) schedule built for a larger fleet than the
    actual worker count must not overflow the staging buffer — k_max is
    sized to the schedule's own ceiling, so the buffer keeps filling
    until the demanded K is reached."""
    num_workers = 2
    schedule = step_schedule(5, 1)         # K(t) can demand up to 5
    params, server = _server(mode="hybrid", num_workers=num_workers,
                             schedule=schedule)
    codec = server.codec
    for i in range(20):
        server.ingest(GradientMsg(i % num_workers,
                                  codec.encode(_tree(i, 0.01)),
                                  server.version, i))
    # flush sizes 1,2,3,4,5,5 — every gradient accounted, none clobbered
    assert server.applied == 20 and len(server.buffer) == 0
    assert server.agg.flush_cache_size() == 1


def test_async_flushes_every_gradient_regardless_of_schedule():
    """async is K ≡ 1 by definition: its one-row staging buffer relies
    on the schedule being ignored, whatever K it would demand."""
    params, server = _server(mode="async", num_workers=3,
                             schedule=step_schedule(3, 1))
    codec = server.codec
    for i in range(6):
        server.ingest(GradientMsg(i % 3, codec.encode(_tree(i, 0.01)),
                                  server.version, i))
    assert server.applied == server.version == 6
    assert server.agg.k_max == 1


@pytest.mark.parametrize("num_workers", [1, 3, 5])
def test_exactly_one_flush_executable_any_fleet(num_workers):
    """The jit-cache probe: after serving traffic across every buffer
    size K in 1..fleet, the server holds exactly ONE compiled flush
    executable (the pre-slab server compiled ``num_workers`` of them
    before the clock even started)."""
    schedule = step_schedule(num_workers, 1)       # K grows every update
    params, server = _server(mode="hybrid", num_workers=num_workers,
                             schedule=schedule)
    codec = server.codec
    seen_k = set()
    for i in range(4 * num_workers):
        k_now = schedule(server.version)
        seen_k.add(k_now)
        server.ingest(GradientMsg(i % num_workers,
                                  codec.encode(_tree(i, 0.01)),
                                  server.version, i))
    assert seen_k == set(range(1, num_workers + 1))  # every K exercised
    assert server.agg.flush_cache_size() == 1
    assert server.applied == 4 * num_workers


# ------------------------------------------- slab-resident optimizers

from repro.core.slab import slab_codec as _slab_codec  # noqa: E402

OPTS = [SlabOptimizer("sgd"),
        SlabOptimizer("momentum", beta1=0.9),
        SlabOptimizer("adamw", beta1=0.9, beta2=0.95, weight_decay=0.01)]


def test_sgd_optimizer_flush_bitwise_identical_to_legacy():
    """The hard invariant: optimizer="sgd" IS the historical flush, bit
    for bit — an explicitly-passed sgd SlabOptimizer changes nothing
    against the pre-refactor fused aggregate+apply."""
    num_workers = 3
    params, server = _server(mode="sync", num_workers=num_workers,
                             optimizer=SlabOptimizer("sgd"))
    for w in range(num_workers):
        server.register(w)
    codec = server.codec
    p = params
    for r in range(4):
        grads = [_tree(10 * r + w, 0.01) for w in range(num_workers)]
        for w in range(num_workers):
            server.ingest(GradientMsg(w, codec.encode(grads[w]),
                                      server.version, r))
        p = legacy_agg_apply(p, tuple(grads), np.ones(num_workers),
                             server.lr)
    _, got, _ = server.snapshot()
    for name in params:
        np.testing.assert_array_equal(np.asarray(got[name]),
                                      np.asarray(p[name]), err_msg=name)
    assert server.agg.opt_state_host() is None     # sgd carries no state


@pytest.mark.parametrize("opt", OPTS, ids=lambda o: o.name)
def test_sim_and_cluster_sync_flush_bitwise_identical(opt):
    """The simulator and the cluster server run the SAME fused
    flush+optimizer executable: staging the same sync rounds through a
    simulator-style aggregator (PSTrainer's construction, no server
    warmup) and through a live ParameterServer yields bitwise-identical
    params AND moments, per optimizer."""
    num_workers = 3
    params, server = _server(mode="sync", num_workers=num_workers,
                             optimizer=opt)
    for w in range(num_workers):
        server.register(w)
    # the simulator path: PSTrainer builds its aggregator exactly so
    # (and never warmups — the server's warmup must be a bitwise no-op)
    sim_agg = SlabAggregator(_slab_codec(params), params, num_workers,
                             optimizer=opt)
    for r in range(5):
        grads = [_tree(10 * r + w, 0.01) for w in range(num_workers)]
        for w in range(num_workers):
            server.ingest(GradientMsg(w, server.codec.encode(grads[w]),
                                      server.version, r))
            sim_agg.stage(sim_agg.codec.encode(grads[w]), w)
        sim_agg.flush_apply(np.ones(num_workers), server.lr)
    _, got, _ = server.snapshot()
    want = sim_agg.params_tree()
    for name in params:
        np.testing.assert_array_equal(np.asarray(got[name]),
                                      np.asarray(want[name]),
                                      err_msg=f"{opt.name}:{name}")
    st_server = server.agg.opt_state_host()
    st_sim = sim_agg.opt_state_host()
    if opt.name == "sgd":
        assert st_server is None and st_sim is None
    else:
        assert st_server["count"] == st_sim["count"] == 5
        for mname in opt.moment_names:
            np.testing.assert_array_equal(st_server[mname],
                                          st_sim[mname],
                                          err_msg=f"{opt.name}:{mname}")


@pytest.mark.parametrize("use_pallas", [False, True],
                         ids=["jnp", "pallas_interpret"])
@pytest.mark.parametrize("opt", OPTS, ids=lambda o: o.name)
def test_flush_below_k_max_matches_the_masked_fold(opt, use_pallas):
    """k < K_max: the empty slot passes slot 0's row at weight 0.  Two
    flushes give what the pre-change masked fold gave — the same
    executable over a ``(K_max, P)`` matrix whose empty row is zeros:
    bitwise on the jnp path; within the fold's rounding in the kernel,
    whose stacked form reduces along K where the rows form folds row
    by row (the two contract to FMAs differently on the CPU)."""
    params = _tree(0)
    codec = slab_codec(params, "bf16")
    kw = dict(use_pallas=use_pallas, interpret=use_pallas, optimizer=opt)
    agg = SlabAggregator(codec, params, k_max=3, **kw)
    ref = SlabAggregator(codec, params, k_max=3, **kw)
    impl = {"sgd": ref._flush_impl, "momentum": ref._flush_momentum_impl,
            "adamw": ref._flush_adamw_impl}[opt.name]
    impl = jax.jit(impl)
    w = np.array([1.0, 0.9], np.float32)
    w_pad, s = jnp.asarray([1.0, 0.9, 0.0], jnp.float32), jnp.float32(0.05)
    state = [ref._moments[n] for n in opt.moment_names]
    count, slab = ref._count, ref._slab
    for step in range(2):
        rows = [codec.encode(_tree(10 * step + i, 0.01)) for i in range(2)]
        for i, r in enumerate(rows):
            agg.stage(r, i)
        got = agg.flush_apply(w, 0.05)
        matrix = jnp.stack(rows + [jnp.zeros_like(rows[0])])
        if opt.name == "sgd":
            slab, want = impl(slab, matrix, w_pad, s)
        else:
            slab, *state, count, want = impl(slab, *state, count, matrix,
                                             w_pad, s)
    pairs = [(agg._slab, slab), (got, want)] + list(zip(
        [agg._moments[n] for n in opt.moment_names], state))
    for a, b in pairs:
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        if use_pallas:
            np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)
        else:
            np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("opt", OPTS[1:], ids=lambda o: o.name)
def test_momentum_adamw_exactly_one_fused_executable(opt):
    """The one-executable contract extends to the optimizer flushes:
    after serving every buffer size K in 1..fleet, momentum/adamw hold
    exactly ONE compiled fused flush+update executable."""
    num_workers = 4
    schedule = step_schedule(num_workers, 1)       # K grows every update
    params, server = _server(mode="hybrid", num_workers=num_workers,
                             schedule=schedule, optimizer=opt)
    codec = server.codec
    seen_k = set()
    for i in range(4 * num_workers):
        seen_k.add(schedule(server.version))
        server.ingest(GradientMsg(i % num_workers,
                                  codec.encode(_tree(i, 0.01)),
                                  server.version, i))
    assert seen_k == set(range(1, num_workers + 1))
    assert server.agg.flush_cache_size() == 1
    # conservation: every ingested gradient is applied or still staged
    assert server.applied + len(server.buffer) == 4 * num_workers


def test_moments_stay_f32_under_bf16_slab():
    """The mixed-precision rule: slab_dtype="bf16" halves the staging/
    wire bytes, but the optimizer moments (like the master params) stay
    f32 — second moments in bf16 would collapse small squared
    gradients to zero."""
    opt = SlabOptimizer("adamw", beta1=0.9, beta2=0.95)
    params, server = _server(mode="async", num_workers=2,
                             slab_dtype="bf16", optimizer=opt)
    codec = server.codec
    assert jnp.asarray(server.agg.params_slab).dtype == jnp.bfloat16
    for i in range(4):
        server.ingest(GradientMsg(i % 2, codec.encode(_tree(i, 0.01)),
                                  server.version, i))
    moments = server.agg._moments
    chunks = []
    for name in opt.moment_names:
        m = moments[name]
        chunks += list(m) if isinstance(m, list) else [m]
    assert chunks and all(c.dtype == jnp.float32 for c in chunks)
    st = server.agg.opt_state_host()
    for name in opt.moment_names:
        assert st[name].dtype == np.float32
        assert np.isfinite(st[name]).all()
    assert st["count"] == 4


def test_opt_state_checkpoint_round_trip_resumes_bitwise(tmp_path):
    """Checkpoint mid-run with adamw, restore into a fresh server, and
    continue: the resumed trajectory is bitwise identical to the
    uninterrupted one — moments AND the bias-correction count travel
    with the params."""
    from repro.checkpoint import (load_opt_state, restore_checkpoint,
                                  save_checkpoint)
    opt = SlabOptimizer("adamw", beta1=0.9, beta2=0.95,
                        weight_decay=0.01)
    params, server_a = _server(mode="async", num_workers=2,
                               optimizer=opt)
    codec = server_a.codec
    grads = [codec.encode(_tree(50 + i, 0.01)) for i in range(6)]
    for i in range(3):
        server_a.ingest(GradientMsg(i % 2, grads[i],
                                    server_a.version, i))
    version, snap, _, opt_state = server_a.snapshot_for_checkpoint()
    assert opt_state["count"] == 3
    path = str(tmp_path / f"step_{version}")
    save_checkpoint(path, snap, version, opt_state=opt_state)

    # a fresh server restores params + moments + count from disk
    _, server_b = _server(mode="async", num_workers=2, optimizer=opt)
    r_params, r_step = restore_checkpoint(path, like=params)
    r_opt = load_opt_state(path)
    assert r_opt is not None and r_opt["count"] == 3
    server_b.restore(r_params, r_step, opt_state=r_opt)

    for i in range(3, 6):
        for s in (server_a, server_b):
            s.ingest(GradientMsg(i % 2, grads[i], s.version, i))
    _, got_a, _ = server_a.snapshot()
    _, got_b, _ = server_b.snapshot()
    for name in params:
        np.testing.assert_array_equal(np.asarray(got_a[name]),
                                      np.asarray(got_b[name]),
                                      err_msg=name)
    st_a = server_a.agg.opt_state_host()
    st_b = server_b.agg.opt_state_host()
    assert st_a["count"] == st_b["count"] == 6
    for mname in opt.moment_names:
        np.testing.assert_array_equal(st_a[mname], st_b[mname],
                                      err_msg=mname)


def test_old_checkpoint_without_opt_state_restores_zero_moments(
        tmp_path):
    """Back-compat: a checkpoint written without optimizer state (the
    pre-refactor format, or an sgd run) restores cleanly — moments
    restart from zero, count from 0."""
    from repro.checkpoint import load_opt_state, save_checkpoint
    opt = SlabOptimizer("momentum", beta1=0.9)
    params, server = _server(mode="async", num_workers=2, optimizer=opt)
    codec = server.codec
    for i in range(3):
        server.ingest(GradientMsg(i % 2, codec.encode(_tree(i, 0.01)),
                                  server.version, i))
    path = str(tmp_path / "step_0")
    save_checkpoint(path, params, 0)           # no opt_state (old form)
    assert load_opt_state(path) is None
    server.restore(params, 0, opt_state=load_opt_state(path))
    st = server.agg.opt_state_host()
    assert st["count"] == 0
    assert not np.any(st["mu"])                # zeroed, not stale
