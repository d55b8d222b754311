"""Versioned sparse delta broadcast (DESIGN.md §2.10): checksum +
non-finite guards, staleness contract, publisher error feedback, resync
protocol, fault-injected channels, and the in-flight pinned-decode
consistency invariant.

The contract every fault case pins: a replica either holds version v
with params BIT-EQUAL to the publisher's params-at-v, or is mid-resync
and refuses to advance. No injected fault may crash the replica or let
unhealthy values reach live params.
"""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import faults
from repro.serve.delta import (DeltaApplier, DeltaPayload, DeltaPublisher,
                               DeltaVersionError, FaultyChannel,
                               MemoryChannel, SpoolChannel, delta_wire_bytes,
                               drain, payload_checksum, payload_health,
                               read_snapshot, resync_bytes,
                               resync_equiv_deltas, scatter_set_tree,
                               write_snapshot)


def _tree(key, dtype=jnp.float32):
    ks = jax.random.split(key, 3)
    return {
        "w": jax.random.normal(ks[0], (16, 8), dtype),
        "nested": {"b": jax.random.normal(ks[1], (11,), dtype)},
        "head": jax.random.normal(ks[2], (5, 5), dtype),
    }


def _walk(params, t, scale=0.05):
    """Deterministic trainer step: params + seeded noise."""
    k = jax.random.PRNGKey(1000 + t)
    leaves, td = jax.tree_util.tree_flatten(params)
    new = [l + (scale * jax.random.normal(
        jax.random.fold_in(k, i), l.shape)).astype(l.dtype)
        for i, l in enumerate(leaves)]
    return jax.tree_util.tree_unflatten(td, new)


def _assert_trees_equal(a, b, msg=""):
    for x, y in zip(jax.tree_util.tree_leaves(a),
                    jax.tree_util.tree_leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=msg)


# ---------------------------------------------------------------------------
# Payload integrity: checksum + non-finite guards
# ---------------------------------------------------------------------------

def _payload(version=1, k=6, j=100, seed=0):
    rng = np.random.default_rng(seed)
    vals = rng.normal(size=k).astype(np.float32)
    idx = np.sort(rng.choice(j, size=k, replace=False)).astype(np.int32)
    return DeltaPayload.stamp(version, vals, idx, k, j)


def test_checksum_detects_any_single_flip():
    p = _payload()
    assert p.verify() == "ok"
    # value bit flip
    v = np.array(p.values, copy=True)
    v.view(np.uint32)[2] ^= 1 << 13
    assert dataclasses.replace(p, values=v).verify() == "corrupt"
    # index bit flip
    i = np.array(p.indices, copy=True)
    i[3] ^= 1 << 2
    assert dataclasses.replace(p, indices=i).verify() == "corrupt"
    # header tampering: version, count, j all feed the sum
    assert dataclasses.replace(p, version=p.version + 1).verify() == "corrupt"
    assert dataclasses.replace(p, count=p.count - 1).verify() == "corrupt"
    assert dataclasses.replace(p, j=p.j + 1).verify() == "corrupt"
    # swapped entries: position weights catch value permutations that
    # a plain sum would miss
    v2 = np.array(p.values, copy=True)
    v2[[0, 1]] = v2[[1, 0]]
    assert dataclasses.replace(p, values=v2).verify() == "corrupt"


def test_checksum_position_weighted_and_index_range():
    p = _payload(j=50)
    # out-of-range index with a RE-STAMPED checksum is still corrupt
    i = np.array(p.indices, copy=True)
    i[0] = 50
    bad = DeltaPayload.stamp(p.version, p.values, i, p.count, p.j)
    assert bad.verify() == "corrupt"
    # shape mismatch
    assert dataclasses.replace(p, values=p.values[:3]).verify() == "corrupt"


def test_nonfinite_is_distinct_from_corrupt():
    """A checksum-VALID payload carrying NaN is publisher poison, not
    transport damage — distinct verdict, distinct counter."""
    p = _payload()
    v = np.array(p.values, copy=True)
    v[1] = np.nan
    poisoned = DeltaPayload.stamp(p.version, v, p.indices, p.count, p.j)
    assert poisoned.verify() == "nonfinite"
    v[1] = np.inf
    assert DeltaPayload.stamp(p.version, v, p.indices, p.count,
                              p.j).verify() == "nonfinite"


def test_payload_health_traced_safe():
    """payload_health is the jit/psum-able form of verify()."""
    p = _payload()
    f = jax.jit(payload_health)
    csum = np.uint32(p.checksum)
    ok, corrupt, nonfinite = f(p.values, p.indices, csum,
                               p.version, p.count, p.j)
    assert bool(ok) and not bool(corrupt) and not bool(nonfinite)
    v = np.array(p.values, copy=True)
    v.view(np.uint32)[0] ^= 1 << 7
    ok, corrupt, _ = f(v, p.indices, csum, p.version, p.count, p.j)
    assert not bool(ok) and bool(corrupt)
    v = np.array(p.values, copy=True)
    v[0] = np.nan
    csum = np.uint32(payload_checksum(v, p.indices, p.version, p.count, p.j))
    ok, corrupt, nonfinite = f(v, p.indices, csum, p.version, p.count, p.j)
    assert not bool(ok) and not bool(corrupt) and bool(nonfinite)


# ---------------------------------------------------------------------------
# Publisher -> applier exact tracking (the §2.10 invariant, clean channel)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_publish_apply_bitwise_tracking(dtype):
    """Replica at accepted version v is bit-identical to the publisher's
    params-at-v — in fp32 AND bf16 leaves (values round-trip through the
    fp32 wire and cast at the leaf on both sides)."""
    params = _tree(jax.random.PRNGKey(0), dtype)
    pub = DeltaPublisher(params, k=20, record_history=True)
    app = DeltaApplier(params)
    cur = params
    for t in range(12):
        cur = _walk(cur, t)
        payload = pub.publish(cur)
        assert app.offer(payload) == "applied"
        assert app.version == pub.version
        _assert_trees_equal(app.params, pub.params_at(app.version),
                            msg=f"v{app.version} dtype={dtype}")


def test_error_feedback_drains_residual():
    """Coordinates the k-budget skipped stay in the publisher's residual:
    after the trainer STOPS moving, ceil(j/k) more publishes bring the
    replica exactly to the true params."""
    params = _tree(jax.random.PRNGKey(1))
    j = sum(int(x.size) for x in jax.tree_util.tree_leaves(params))
    k = 16
    pub = DeltaPublisher(params, k=k)
    app = DeltaApplier(params)
    final = _walk(_walk(params, 0, scale=0.5), 1, scale=0.5)
    for _ in range(math.ceil(j / k)):
        app.offer(pub.publish(final))
    _assert_trees_equal(app.params, final)


def test_scatter_set_is_idempotent():
    """Wire values are ABSOLUTE (scatter-SET): applying the same payload
    twice is a no-op, which is what makes redelivery harmless."""
    params = _tree(jax.random.PRNGKey(2))
    from repro.core.flatten import TreeFlattener
    flat = TreeFlattener(params)
    vals = jnp.linspace(1.0, 2.0, 7)
    idx = jnp.asarray([0, 5, 40, 127, 128, 140, 152], jnp.int32)
    once = scatter_set_tree(flat, params, vals, idx)
    twice = scatter_set_tree(flat, once, vals, idx)
    _assert_trees_equal(once, twice)


# ---------------------------------------------------------------------------
# Staleness contract: stale drop, gap -> refuse -> resync
# ---------------------------------------------------------------------------

def test_stale_dropped_gap_refuses_until_resync(tmp_path):
    params = _tree(jax.random.PRNGKey(3))
    pub = DeltaPublisher(params, k=20, record_history=True)
    app = DeltaApplier(params)
    snap = str(tmp_path)
    cur = params
    payloads = []
    for t in range(6):
        cur = _walk(cur, t)
        payloads.append(pub.publish(cur))
    assert app.offer(payloads[0]) == "applied"
    # redelivery of an applied version is stale, not an error
    assert app.offer(payloads[0]) == "stale"
    assert app.counters["dropped_stale"] == 1
    # v3 on top of v1 is a gap: flips needs_resync, params untouched
    before = app.params
    assert app.offer(payloads[2]) == "gap"
    assert app.needs_resync and app.counters["gaps_detected"] == 1
    _assert_trees_equal(app.params, before)
    # EVERYTHING is refused mid-resync, even the in-order v2
    assert app.offer(payloads[1]) == "resync_pending"
    assert app.offer(payloads[3]) == "resync_pending"
    # no snapshot yet -> cannot resync; equal-version snapshot neither
    assert not app.can_resync(snap)
    write_snapshot(snap, pub.params_at(1), 1)
    assert not app.can_resync(snap)
    # a NEWER snapshot re-arms intake and raises the floor
    pub.write_snapshot(snap)     # v6
    assert app.can_resync(snap)
    assert app.resync_from(snap) == 6
    assert app.version == 6 and app.floor == 6 and not app.needs_resync
    _assert_trees_equal(app.params, pub.params_at(6))
    # post-resync: old versions are stale, the next contiguous applies
    assert app.offer(payloads[3]) == "stale"
    cur = _walk(cur, 99)
    assert app.offer(pub.publish(cur)) == "applied"
    _assert_trees_equal(app.params, pub.params_at(7))


def test_resync_never_moves_backwards(tmp_path):
    params = _tree(jax.random.PRNGKey(4))
    pub = DeltaPublisher(params, k=20)
    app = DeltaApplier(params)
    old = str(tmp_path / "old")
    write_snapshot(old, params, 0)
    cur = params
    for t in range(3):
        cur = _walk(cur, t)
        app.offer(pub.publish(cur))
    assert app.version == 3
    with pytest.raises(DeltaVersionError, match="backwards"):
        app.resync_from(old, step=0)


def test_strict_apply_raises_on_violations(tmp_path):
    params = _tree(jax.random.PRNGKey(5))
    pub = DeltaPublisher(params, k=20)
    app = DeltaApplier(params)
    cur = _walk(params, 0)
    p1 = pub.publish(cur)
    cur = _walk(cur, 1)
    p2 = pub.publish(cur)
    # out of order
    with pytest.raises(DeltaVersionError, match="contiguous"):
        app.apply(p2)
    # corrupt
    v = np.array(p1.values, copy=True)
    v.view(np.uint32)[0] ^= 1
    with pytest.raises(DeltaVersionError, match="corrupt"):
        app.apply(dataclasses.replace(p1, values=v))
    # j mismatch (payload from another model)
    with pytest.raises(DeltaVersionError):
        app.apply(DeltaPayload.stamp(1, p1.values, p1.indices, p1.count,
                                     p1.j + 64))
    app.apply(p1)
    app.apply(p2)
    assert app.version == 2


def test_nonfinite_never_reaches_live_params():
    params = _tree(jax.random.PRNGKey(6))
    pub = DeltaPublisher(params, k=20)
    app = DeltaApplier(params)
    p1 = pub.publish(_walk(params, 0))
    v = np.array(p1.values, copy=True)
    v[0] = np.nan
    poisoned = DeltaPayload.stamp(p1.version, v, p1.indices, p1.count, p1.j)
    before = app.params
    assert app.offer(poisoned) == "nonfinite"
    assert app.counters["dropped_nonfinite"] == 1
    _assert_trees_equal(app.params, before)
    assert all(np.all(np.isfinite(np.asarray(l, np.float32)))
               for l in jax.tree_util.tree_leaves(app.params))
    # the dropped version then shows up as a gap when v2 arrives
    assert app.offer(pub.publish(_walk(params, 1))) == "gap"
    with pytest.raises(DeltaVersionError, match="nonfinite"):
        app.apply(poisoned)


# ---------------------------------------------------------------------------
# Checkpoint floor: deltas predating a restore are a hard error
# ---------------------------------------------------------------------------

def test_version_floor_from_restored_snapshot(tmp_path):
    params = _tree(jax.random.PRNGKey(7))
    pub = DeltaPublisher(params, k=20)
    snap = str(tmp_path)
    cur = params
    old_payloads = []
    for t in range(5):
        cur = _walk(cur, t)
        old_payloads.append(pub.publish(cur))
    pub.write_snapshot(snap)     # v5
    restored, version = read_snapshot(snap, params)
    assert version == 5
    app = DeltaApplier(restored, version=version)
    assert app.floor == 5
    for p in old_payloads:
        with pytest.raises(DeltaVersionError, match="floor"):
            app.apply(p)
    # at-floor is just as illegal as below-floor
    with pytest.raises(DeltaVersionError, match="floor"):
        app.apply(old_payloads[-1])
    cur = _walk(cur, 5)
    app.apply(pub.publish(cur))
    assert app.version == 6


# ---------------------------------------------------------------------------
# Transports
# ---------------------------------------------------------------------------

def test_spool_channel_roundtrip(tmp_path):
    root = str(tmp_path)
    tx, rx = SpoolChannel(root), SpoolChannel(root)
    ps = [_payload(version=v, seed=v) for v in (1, 2, 3)]
    for p in ps:
        tx.send(p)
    got = rx.recv()
    assert [g.version for g in got] == [1, 2, 3]
    for a, b in zip(ps, got):
        assert b.verify() == "ok"
        np.testing.assert_array_equal(a.values, b.values)
        np.testing.assert_array_equal(a.indices, b.indices)
        assert (a.version, a.count, a.j, a.checksum) == \
            (b.version, b.count, b.j, b.checksum)
    # receiver remembers its position; sender sequence survives restart
    assert rx.recv() == []
    SpoolChannel(root).send(_payload(version=4, seed=4))
    assert [g.version for g in rx.recv()] == [4]


def test_memory_channel_fifo():
    ch = MemoryChannel()
    for v in (1, 2):
        ch.send(_payload(version=v))
    assert [p.version for p in ch.recv()] == [1, 2]
    assert ch.recv() == []


def test_faulty_channel_one_sided_injection():
    """A wrapper used on the SEND side must not re-inject on recv —
    an even number of identical bit flips cancels out."""
    sched = faults.parse_channel_schedule("corrupt:0.999,seed=1")
    ch = FaultyChannel(MemoryChannel(), sched)
    p = _payload(version=1)
    ch.send(p)
    (got,) = ch.recv()
    assert got.verify() == "corrupt"   # flipped exactly once


# ---------------------------------------------------------------------------
# Channel fault schedules (core/faults.py)
# ---------------------------------------------------------------------------

def test_channel_schedule_parse_format_roundtrip():
    for spec in ("loss:0.3,seed=5", "corrupt:0.01,seed=0",
                 "reorder:4,seed=2", "stall:10,every=50,at=20"):
        s = faults.parse_channel_schedule(spec)
        assert faults.parse_channel_schedule(
            faults.format_channel_schedule(s)) == s
    assert faults.parse_channel_schedule("") is None
    assert faults.parse_channel_schedule("none") is None
    assert faults.format_channel_schedule(None) == ""
    # keyword form == bare form
    assert faults.parse_channel_schedule("loss:p=0.3") == \
        faults.parse_channel_schedule("loss:0.3")


def test_channel_schedule_rejects_bad_specs():
    for bad in ("jitter:0.5", "loss:1.0", "loss:-0.1", "reorder:0",
                "stall:0", "stall:10,every=5", "loss:0.1,huh"):
        with pytest.raises(ValueError):
            faults.parse_channel_schedule(bad)


def test_channel_decisions_deterministic_and_seeded():
    s1 = faults.parse_channel_schedule("loss:0.5,seed=3")
    s2 = faults.parse_channel_schedule("loss:0.5,seed=4")
    d1 = [bool(faults.channel_drops(s1, v)) for v in range(64)]
    assert d1 == [bool(faults.channel_drops(s1, v)) for v in range(64)]
    assert d1 != [bool(faults.channel_drops(s2, v)) for v in range(64)]
    assert 0.25 < np.mean(d1) < 0.75
    r = faults.parse_channel_schedule("reorder:3,seed=1")
    delays = [int(faults.channel_delay(r, v)) for v in range(64)]
    assert min(delays) >= 0 and max(delays) <= 3 and max(delays) > 0
    st = faults.parse_channel_schedule("stall:5,at=3")
    stalled = [bool(faults.channel_stalled(st, v)) for v in range(12)]
    assert stalled == [False] * 3 + [True] * 5 + [False] * 4
    per = faults.parse_channel_schedule("stall:2,every=4,at=1")
    assert [bool(faults.channel_stalled(per, v)) for v in range(9)] == \
        [False, True, True, False, False, True, True, False, False]


def test_expected_delivery_rate_and_describe():
    assert faults.expected_delivery_rate(None) == 1.0
    assert faults.expected_delivery_rate(
        faults.parse_channel_schedule("loss:0.2")) == pytest.approx(0.8)
    assert faults.expected_delivery_rate(
        faults.parse_channel_schedule("reorder:4")) == 1.0
    d = faults.describe_channel(faults.parse_channel_schedule("corrupt:0.1"))
    assert d["kind"] == "corrupt"
    assert d["delivery_rate_expected"] == pytest.approx(0.9)
    assert faults.parse_channel_schedule(d["schedule"]) is not None
    import json
    json.dumps(d)


# ---------------------------------------------------------------------------
# The fault-trace invariant: ANY injected fault, replica holds v
# bit-equal to publisher-at-v or is mid-resync
# ---------------------------------------------------------------------------

def _run_faulty(spec, tmp_path, steps=25, snap_every=8, k=24):
    params = _tree(jax.random.PRNGKey(8))
    pub = DeltaPublisher(params, k=k, record_history=True)
    app = DeltaApplier(params)
    chan = FaultyChannel(MemoryChannel(),
                         faults.parse_channel_schedule(spec))
    snap = str(tmp_path / "snaps")
    write_snapshot(snap, params, 0)
    cur = params
    for t in range(steps):
        cur = _walk(cur, t)
        chan.send(pub.publish(cur))
        if pub.version % snap_every == 0:
            pub.write_snapshot(snap)
        drain(chan, app)
        if app.needs_resync and app.can_resync(snap):
            app.resync_from(snap)
        # THE invariant: held version bit-equal to publisher-at-version
        _assert_trees_equal(app.params, pub.params_at(app.version),
                            msg=f"{spec} @ t={t} v{app.version}")
        assert np.all([np.all(np.isfinite(np.asarray(l, np.float32)))
                       for l in jax.tree_util.tree_leaves(app.params)])
    # end of stream: flush the channel, final snapshot, converge
    for p in chan.flush():
        app.offer(p)
    pub.write_snapshot(snap)
    if app.needs_resync and app.can_resync(snap):
        app.resync_from(snap)
    drain(chan, app)
    stream = dict(app.counters)
    # versions lost at the very end have no successor to reveal the gap:
    # only when the channel dropped or corrupted EVERY version after the
    # held one may the replica catch up from the final snapshot
    tail = range(app.version + 1, pub.version + 1)
    if tail and all(bool(faults.channel_drops(chan.sched, v)) or
                    bool(faults.channel_corrupts(chan.sched, v))
                    for v in tail):
        app.resync_from(snap)
    _assert_trees_equal(app.params, pub.params_at(app.version), msg=spec)
    assert app.version == pub.version, (spec, app.metrics())
    return app, chan, stream


def test_invariant_under_loss(tmp_path):
    app, chan, stream = _run_faulty("loss:0.4,seed=2", tmp_path)
    assert chan.counters["dropped"] > 0
    assert stream["gaps_detected"] > 0 and stream["resyncs"] > 0


def test_invariant_under_corruption(tmp_path):
    app, chan, stream = _run_faulty("corrupt:0.4,seed=3", tmp_path)
    assert chan.counters["corrupted"] > 0
    assert app.counters["dropped_corrupt"] == chan.counters["corrupted"]
    assert stream["gaps_detected"] > 0 and stream["resyncs"] > 0


def test_invariant_under_reorder(tmp_path):
    app, chan, _ = _run_faulty("reorder:3,seed=4", tmp_path)
    assert chan.counters["delayed"] > 0
    # reorder delivers everything eventually; anything early is stale
    # or gapped, never applied out of order
    assert app.counters["applied"] + app.counters["dropped_stale"] > 0


def test_invariant_under_stall_no_resync(tmp_path):
    """A paused link flushes IN ORDER: the replica absorbs the backlog
    with zero gaps and zero resyncs."""
    app, chan, _ = _run_faulty("stall:5,at=3", tmp_path)
    assert chan.counters["stalled"] > 0
    assert app.counters["gaps_detected"] == 0
    assert app.counters["resyncs"] == 0
    assert app.counters["applied"] == 25


@pytest.mark.slow
def test_invariant_long_horizon_all_faults(tmp_path):
    """Long-horizon sweep over every fault kind (the CI fault-injection
    lane's delta-channel analogue of the elastic soak test)."""
    for i, spec in enumerate(("loss:0.25,seed=11", "corrupt:0.25,seed=12",
                              "reorder:5,seed=13",
                              "stall:7,every=20,at=5")):
        _run_faulty(spec, tmp_path / f"case{i}", steps=120, snap_every=16)


# ---------------------------------------------------------------------------
# In-flight consistency: pinned decode streams are bit-identical to a
# version-pinned oracle while deltas land between steps
# ---------------------------------------------------------------------------

def test_pinned_decode_unaffected_by_live_applies():
    from repro.configs.base import get_config, reduced_config
    from repro.models import Parallel, decode_step, init_params, prefill
    cfg = reduced_config(get_config("stablelm-3b"))
    pal = Parallel()
    key = jax.random.PRNGKey(0)
    params = init_params(cfg, pal, key)
    B, S, new = 2, 12, 6
    prompt = jax.random.randint(key, (B, S), 0, cfg.vocab_size)
    pre = jax.jit(lambda p, b: prefill(p, b, cfg, pal, max_seq=S + new))
    dec = jax.jit(lambda p, c, t: decode_step(p, c, t, cfg, pal))

    def run(p, interleave):
        """Greedy decode; interleave() fires between steps."""
        logits, cache = pre(p, {"tokens": prompt})
        toks = []
        for _ in range(new):
            nxt = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
            toks.append(np.asarray(nxt))
            logits, cache = dec(p, cache, nxt)
            interleave()
        return toks, np.asarray(logits)

    # oracle: decode at version 0, nothing else happening
    oracle_toks, oracle_logits = run(params, lambda: None)

    pub = DeltaPublisher(params, k=256)
    app = DeltaApplier(params)
    chan = MemoryChannel()
    state = {"cur": params, "t": 0}

    def trainer_step():
        state["cur"] = _walk(state["cur"], state["t"], scale=0.5)
        state["t"] += 1
        chan.send(pub.publish(state["cur"]))
        drain(chan, app)

    pinned, pinned_v = app.acquire()
    assert pinned_v == 0
    live_toks, live_logits = run(pinned, trainer_step)
    # live tree moved...
    assert app.version == new and app.counters["applied"] == new
    # ...but the pinned stream is BIT-identical to the oracle
    np.testing.assert_array_equal(oracle_logits, live_logits)
    for a, b in zip(oracle_toks, live_toks):
        np.testing.assert_array_equal(a, b)
    # and a stream acquired NOW starts from the advanced version
    _, v2 = app.acquire()
    assert v2 == new


# ---------------------------------------------------------------------------
# Analytic costs (dryrun record + roofline terms)
# ---------------------------------------------------------------------------

def test_wire_cost_helpers():
    assert delta_wire_bytes(1024) == 1024 * 8 + 24
    assert resync_bytes(10_000) == 40_024
    r = resync_equiv_deltas(1_000_000, 1024)
    assert r == pytest.approx(4_000_024 / (1024 * 8 + 24))


def test_roofline_delta_terms():
    from repro.roofline.analysis import HW_V5E, roofline_terms
    rec = {
        "mesh": {"data": 4, "model": 2},
        "kind": "decode", "shape": "decode_32k",
        "active_params": 3_000_000_000,
        "flops": 1e12, "bytes_accessed": 1e11,
        "collective_bytes": {"total": 1e9},
        "delta": {"k": 4096,
                  "wire_bytes": delta_wire_bytes(4096),
                  "resync_bytes": resync_bytes(3_000_000_000),
                  "resync_equiv_deltas":
                      resync_equiv_deltas(3_000_000_000, 4096),
                  "fault": faults.describe_channel(
                      faults.parse_channel_schedule("loss:0.05"))},
    }
    t = roofline_terms(rec, HW_V5E)
    assert t["delta_wire_bytes"] == delta_wire_bytes(4096)
    assert t["delta_bcast_s"] == pytest.approx(
        delta_wire_bytes(4096) / HW_V5E.ici_bw)
    assert t["delta_apply_s"] == pytest.approx(16.0 * 4096 / HW_V5E.hbm_bw)
    assert t["resync_s"] == pytest.approx(
        resync_bytes(3_000_000_000) / HW_V5E.ici_bw)
    assert t["delta_delivery_rate"] == pytest.approx(0.95)
    # losing 5% of versions costs 5% of a resync-per-delta, amortized
    assert t["delta_wire_bytes_effective"] > t["delta_wire_bytes"]
    # clean channel: no effective-rate terms
    clean = dict(rec, delta=dict(rec["delta"], fault=None))
    tc = roofline_terms(clean, HW_V5E)
    assert "delta_delivery_rate" not in tc


def test_dryrun_record_carries_delta_costs(tmp_path):
    """CLI-level: --delta-k/--delta-fault-schedule land in the dryrun
    record with the analytic wire/resync costs (subprocess for device-
    count isolation, like test_system)."""
    import json
    import os
    import subprocess
    import sys
    root = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
    out_json = str(tmp_path / "dr.json")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(root, "src") + os.pathsep + env.get(
        "PYTHONPATH", "")
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch",
         "xlstm-125m", "--shape", "decode_32k", "--mesh", "2x2",
         "--delta-k", "4096", "--delta-fault-schedule", "loss:0.05",
         "--out", out_json],
        capture_output=True, text=True, timeout=1500, env=env, cwd=root)
    assert out.returncode == 0, (
        f"STDOUT:\n{out.stdout[-4000:]}\nSTDERR:\n{out.stderr[-4000:]}")
    (rec,) = json.load(open(out_json))["results"]
    d = rec["delta"]
    assert d["k"] == 4096
    assert d["wire_bytes"] == delta_wire_bytes(4096)
    assert d["resync_equiv_deltas"] > 1
    assert d["fault"]["kind"] == "loss"
    assert d["fault"]["delivery_rate_expected"] == pytest.approx(0.95)
