"""Per-bucket pipelined gossip transport (parallel/collectives).

The kernel lane now partitions the payload leaves into contiguous,
byte-bounded transport buckets (``_transport_plan``) and launches one
split start/wait kernel program per bucket.  Bucketing is a transport
*pipelining* knob: it must never change the round's mathematics, its
wire volume, or the schedule object SGPV106 verifies.  Pinned here:

* plan invariants — contiguity, byte bounding, scalar exclusion, int8
  whole-block padding, clamping, dtype boundaries;
* the scalar/ppermute fallback — a tree with no payload leaf never
  builds a plan, a handle, or a kernel call;
* the FIFO lifecycle seams (``empty_incoming`` / ``land_shares`` /
  ``settle_share``) and their structural cond-branch contract;
* the jit trajectory against a numpy push-sum oracle at staleness
  1–3 × buckets {1, 3} on the world-8 mesh;
* buckets {1, 3} produce BIT-identical trajectories (packing is a
  partition, never a re-quantization);
* ``verify_schedule`` (SGPV106) sees the same object regardless of
  bucket count — the plan is schedule-free by construction.

Compiled mesh dispatch is serialized per the PR-8 deadlock note.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from stochastic_gradient_push_tpu.algorithms import sgp
from stochastic_gradient_push_tpu.analysis import verify_schedule
from stochastic_gradient_push_tpu.ops.gossip_kernel import KernelLane
from stochastic_gradient_push_tpu.parallel import wire
from stochastic_gradient_push_tpu.parallel.collectives import (
    PendingShares,
    _transport_plan,
    empty_incoming,
    land_shares,
    settle_share,
)
from stochastic_gradient_push_tpu.parallel.mesh import (
    GOSSIP_AXIS,
    make_gossip_mesh,
)
from stochastic_gradient_push_tpu.topology import RingGraph, build_schedule

WORLD = 8
ROUNDS = 4

F32_SPEC = wire.F32.kernel_spec()
I8_SPEC = wire.Int8Codec(64).kernel_spec()


# -- the static plan (host-only, no mesh) -----------------------------------


class TestTransportPlan:
    def test_partition_is_contiguous_and_skips_scalars(self):
        leaves = [np.zeros(10, np.float32), np.zeros((), np.float32),
                  np.zeros(33, np.float32), np.zeros(5, np.float32),
                  np.zeros(1, np.float32)]
        plan = _transport_plan(leaves, F32_SPEC, 2)
        slots = [j for bucket in plan for j, _, _ in bucket]
        assert slots == [0, 2, 3]  # contiguous slot order, scalars out
        assert all(n == p for b in plan for _, n, p in b)  # f32: no pad
        assert 1 <= len(plan) <= 2

    def test_byte_bounded_split(self):
        leaves = [np.zeros(100, np.float32) for _ in range(4)]
        plan = _transport_plan(leaves, F32_SPEC, 2)
        assert len(plan) == 2
        sizes = [sum(p for _, _, p in b) for b in plan]
        assert sizes == [200, 200]  # greedy cumulative close balances

    def test_bucket_count_clamps_to_payload_leaves(self):
        leaves = [np.zeros(8, np.float32) for _ in range(3)]
        assert len(_transport_plan(leaves, F32_SPEC, 10)) == 3
        assert len(_transport_plan(leaves, F32_SPEC, 1)) == 1
        with_scalar = leaves + [np.zeros((), np.float32)]
        assert len(_transport_plan(with_scalar, F32_SPEC, 10)) == 3

    def test_int8_leaves_pad_to_whole_blocks(self):
        leaves = [np.zeros(100, np.float32), np.zeros(64, np.float32)]
        plan = _transport_plan(leaves, I8_SPEC, 1)
        assert plan == (((0, 100, 128), (1, 64, 64)),)

    def test_dtype_change_forces_a_boundary(self):
        # one bucket ships ONE packed accumulator, so a mixed-dtype tree
        # may exceed the requested bucket count
        leaves = [np.zeros(8, np.float32), np.zeros(8, np.float16),
                  np.zeros(8, np.float32)]
        plan = _transport_plan(leaves, F32_SPEC, 1)
        assert [tuple(j for j, _, _ in b) for b in plan] == \
            [(0,), (1,), (2,)]

    def test_no_payload_leaf_means_no_plan(self):
        scalars = [np.zeros((), np.float32), np.zeros(1, np.float32)]
        assert _transport_plan(scalars, F32_SPEC, 4) == ()

    def test_bucketing_partitions_but_never_repads(self):
        # comm-volume invariant: any bucket count yields the SAME
        # (slot, n, padded) triples — bucketing moves boundaries, it
        # never changes what goes on the wire
        leaves = [np.zeros(n, np.float32) for n in (100, 7, 65, 3, 200)]
        for spec in (F32_SPEC, I8_SPEC):
            flat = {b: [t for bucket in
                        _transport_plan(leaves, spec, b)
                        for t in bucket]
                    for b in (1, 2, 3, 4)}
            for b in (2, 3, 4):
                assert flat[b] == flat[1]


# -- FIFO lifecycle seams ---------------------------------------------------


class TestPendingLifecycle:
    sched = build_schedule(RingGraph(WORLD, peers_per_itr=1))
    lane = KernelLane(interpret=True)

    def test_empty_incoming_matches_launch_structure(self):
        tree = {"w": jnp.zeros(96), "b": jnp.zeros(5),
                "s": jnp.zeros(())}
        inc = empty_incoming(tree, self.sched, kernel=self.lane,
                             buckets=3)
        assert isinstance(inc, PendingShares)
        assert len(inc.handles) == len(inc.plan) == 2
        assert inc.plan == _transport_plan(
            jax.tree.leaves(tree), F32_SPEC, 3)
        # without a kernel the slot is plain zeros
        plain = empty_incoming(tree, self.sched)
        assert not isinstance(plain, PendingShares)

    def test_scalar_only_tree_stays_on_the_ppermute_lane(self):
        # the push-sum weight (and any size<=1 leaf) must never build a
        # transport handle — the skip branch hands lax.cond plain zeros
        tree = {"w": jnp.zeros(()), "n": jnp.zeros(1)}
        inc = empty_incoming(tree, self.sched, kernel=self.lane,
                             buckets=4)
        assert not isinstance(inc, PendingShares)
        assert all(np.all(np.asarray(v) == 0)
                   for v in jax.tree.leaves(inc))

    def test_settling_a_zero_pending_lands_zero(self):
        # waiting an empty handle contributes decode(0) == 0 — the
        # structural zero the thinning skip branch relies on
        tree = {"w": jnp.ones(96), "b": jnp.ones(5)}
        inc = empty_incoming(tree, self.sched, kernel=self.lane,
                             buckets=2)
        assert isinstance(inc, PendingShares)
        settled = settle_share(inc)
        assert not isinstance(settled, PendingShares)
        for leaf in jax.tree.leaves(settled):
            np.testing.assert_array_equal(np.asarray(leaf), 0.0)
        landed = land_shares(tree, inc)
        for a, b in zip(jax.tree.leaves(landed), jax.tree.leaves(tree)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_land_rejects_mismatched_tree(self):
        tree = {"w": jnp.ones(96), "b": jnp.ones(5)}
        inc = empty_incoming(tree, self.sched, kernel=self.lane)
        with pytest.raises(ValueError, match="mirror"):
            land_shares({"w": jnp.ones(96)}, inc)


# -- trajectory oracle on the world-8 mesh ----------------------------------


def _run(sched, staleness, buckets, rounds=ROUNDS, overlap=True,
         codec=None, ef=False):
    """ROUNDS kernel-lane gossip steps; returns (params [W, D],
    ps-weight trajectory [rounds, W])."""
    alg = sgp(sched, GOSSIP_AXIS, wire=codec, error_feedback=ef,
              overlap=overlap, staleness=staleness,
              gossip_kernel=KernelLane(interpret=True),
              gossip_buckets=buckets)

    def step(p, g):
        p, g = alg.pre_step(p, g)
        return alg.post_step(p, g)

    mesh = make_gossip_mesh(WORLD)
    fn = jax.jit(jax.shard_map(step, mesh=mesh,
                               in_specs=(P(GOSSIP_AXIS),) * 2,
                               out_specs=(P(GOSSIP_AXIS),) * 2,
                               check_vma=False))
    rng = np.random.default_rng(3)
    params = {"w": rng.normal(size=(WORLD, 24)).astype(np.float32),
              "b": rng.normal(size=(WORLD, 5)).astype(np.float32)}
    gstate = jax.tree.map(
        lambda a: np.broadcast_to(np.asarray(a),
                                  (WORLD,) + np.shape(a)).copy(),
        alg.init(jax.tree.map(lambda a: jnp.zeros(a.shape[1:], a.dtype),
                              params)))
    traj = []
    for _ in range(rounds):
        params, gstate = jax.block_until_ready(fn(params, gstate))
        traj.append(np.asarray(gstate.ps_weight).reshape(WORLD).copy())
    return jax.tree.map(np.asarray, params), np.stack(traj)


def _numpy_overlap(sched, trees, w0, rounds, staleness):
    """Float64 push-sum overlap reference: launch ``(W_t − L_t)x_t`` at
    step ``t``, keep ``L_t x_t``, consume the share launched
    ``staleness − 1`` steps earlier (zero before warm-up)."""
    xs = [t.astype(np.float64).copy() for t in trees]
    wv = w0.astype(np.float64).copy()
    lag = staleness - 1
    shares, traj = [], []
    for t in range(rounds):
        W = sched.mixing_matrix(t)
        lo = np.diag(W)
        E = W - np.diag(lo)
        shares.append(([E @ x for x in xs], E @ wv))
        xs = [lo[:, None] * x for x in xs]
        wv = lo * wv
        if t - lag >= 0:
            sp, sw = shares[t - lag]
            xs = [x + s for x, s in zip(xs, sp)]
            wv = wv + sw
        traj.append(wv.copy())
    return xs, np.stack(traj)


def test_trajectory_matches_numpy_oracle_across_staleness_and_buckets():
    """The compiled kernel-lane round equals the dense-matrix push-sum
    reference at every (staleness, buckets) cell — bucketing and the
    split transport change HOW bytes move, never what arrives when."""
    sched = build_schedule(RingGraph(WORLD, peers_per_itr=1))
    for staleness in (1, 2, 3):
        for buckets in (1, 3):
            p, w = _run(sched, staleness, buckets)
            rng = np.random.default_rng(3)
            x0 = [rng.normal(size=(WORLD, 24)).astype(np.float32),
                  rng.normal(size=(WORLD, 5)).astype(np.float32)]
            # dict flatten order is sorted keys: "b" then "w"
            (rb, rw), wref = _numpy_overlap(
                sched, [x0[1], x0[0]], np.ones(WORLD), ROUNDS, staleness)
            label = f"staleness={staleness} buckets={buckets}"
            np.testing.assert_allclose(
                w, wref, atol=1e-6,
                err_msg=f"[{label}] ps-weight trajectory")
            np.testing.assert_allclose(
                p["w"], rw, atol=1e-5,
                err_msg=f"[{label}] params leaf 'w'")
            np.testing.assert_allclose(
                p["b"], rb, atol=1e-5,
                err_msg=f"[{label}] params leaf 'b'")


def test_bucket_count_is_bitwise_invisible():
    """buckets ∈ {1, 3} produce BIT-identical params and ps-weight on
    the same lane — packing concatenates and slices, it never reorders
    a leaf's arithmetic (int8 + EF + overlap is the harshest packing:
    block scales and the telescoping residual both cross the seam)."""
    sched = build_schedule(RingGraph(WORLD, peers_per_itr=1))
    i8 = wire.Int8Codec(64)
    for codec, ef, overlap, s in [(None, False, False, 1),
                                  (i8, True, True, 2)]:
        p1, w1 = _run(sched, s, 1, overlap=overlap, codec=codec, ef=ef)
        p3, w3 = _run(sched, s, 3, overlap=overlap, codec=codec, ef=ef)
        np.testing.assert_array_equal(w1, w3)
        for leaf in p1:
            np.testing.assert_array_equal(p1[leaf], p3[leaf])


def test_sgpv106_object_is_bucket_free():
    """SGPV106 verifies the augmented overlap schedule — an object the
    transport plan never touches (``_transport_plan`` takes leaves and a
    wire spec, no schedule), so bucketing cannot perturb the verified
    contraction.  Pin both halves: the verifier stays green on the
    schedule this file runs, and the plan is a pure function of the
    payload."""
    sched = build_schedule(RingGraph(WORLD, peers_per_itr=1))
    for s in (1, 2, 3):
        ov = sched.overlap_schedule(s)
        findings, gap = verify_schedule(ov, f"ring8-s{s}", "<test>", 1)
        assert not findings, [str(f) for f in findings]
        assert np.isfinite(gap) and gap > 0
    leaves = [np.zeros(96, np.float32), np.zeros(5, np.float32)]
    assert _transport_plan(leaves, F32_SPEC, 3) == \
        _transport_plan(list(leaves), F32_SPEC, 3)
