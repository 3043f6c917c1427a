"""Split Pallas gossip transport (ops/gossip_kernel.py — paired
start/wait ops plus the fused axpy composition): kernel-vs-XLA
bit-parity across sync, overlap and bucketed rounds, chunking,
resolver contracts, and flag plumbing.

The parity sweep runs both transport lanes of the SAME algorithm
configuration on the world-8 CPU mesh — the kernel through the Pallas
interpreter (the real remote-DMA kernel path, discharged over the mesh
axis), the fallback through ``lax.ppermute`` + ``WireCodec.decode`` —
and requires the push-sum weight trajectory BIT-IDENTICAL (the scalar
lane never enters the kernel) and params within f32 tolerance (the only
permitted difference is XLA fusing the receive axpy into an FMA on the
fallback lane).

Dispatch is serialized (every call drains before the next, per the PR-8
CPU-collective deadlock note), and the sweep lives in ONE test so two
compiled mesh programs never run concurrently.

Every ``shard_map`` that holds an INTERPRETED kernel is built with
``check_vma=False``: the Pallas interpreters evaluate the kernel body on
the enclosing trace's own values and cannot type its mix of varying and
unvarying operands (jax 0.9; jax's own remote-DMA tests do the same).
A compiled kernel is an opaque custom call and needs no such thing.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from stochastic_gradient_push_tpu.algorithms import sgp
from stochastic_gradient_push_tpu.ops.gossip_kernel import (
    DEFAULT_CHUNK_ELEMS,
    KernelBackendError,
    KernelLane,
    gossip_edge_axpy,
    resolve_gossip_kernel,
    resolve_use_pallas,
)
from stochastic_gradient_push_tpu.parallel import wire
from stochastic_gradient_push_tpu.parallel.mesh import (
    GOSSIP_AXIS,
    make_gossip_mesh,
)
from stochastic_gradient_push_tpu.resilience import parse_fault_spec
from stochastic_gradient_push_tpu.topology import (
    HierarchicalGraph,
    RingGraph,
    build_schedule,
)
from stochastic_gradient_push_tpu.topology.synthesized import (
    SynthesizedGraph,
)

WORLD = 8
ROUNDS = 4
FAULT_SPEC = "drop:0->1@0:64;seed:7"


def _world_stack(tree):
    return jax.tree.map(
        lambda a: np.broadcast_to(np.asarray(a),
                                  (WORLD,) + np.shape(a)).copy(), tree)


# -- resolver contracts (host-only, no mesh) --------------------------------


class TestResolvers:
    def test_shared_auto_rule(self):
        # on the CPU test backend: auto = interpret only
        assert resolve_use_pallas(None, interpret=True) is True
        assert resolve_use_pallas(None, interpret=False) is \
            (jax.default_backend() == "tpu")
        # an explicit flag always wins
        assert resolve_use_pallas(True, interpret=False) is True
        assert resolve_use_pallas(False, interpret=True) is False

    def test_flag_resolution(self):
        assert resolve_gossip_kernel(None) is None
        assert resolve_gossip_kernel("xla") is None
        lane = resolve_gossip_kernel("auto", interpret=True)
        assert isinstance(lane, KernelLane) and lane.interpret
        assert lane.name == "pallas"
        assert lane.chunk_elems == DEFAULT_CHUNK_ELEMS
        if jax.default_backend() != "tpu":
            assert resolve_gossip_kernel("auto") is None

    def test_pallas_on_cpu_is_a_typed_error(self):
        if jax.default_backend() == "tpu":
            pytest.skip("rejection is the non-TPU contract")
        with pytest.raises(KernelBackendError, match="TPU backend"):
            resolve_gossip_kernel("pallas")
        # interpret mode IS a valid pallas carrier (the test lane)
        assert resolve_gossip_kernel("pallas", interpret=True) is not None

    def test_unknown_flag(self):
        with pytest.raises(ValueError, match="unknown gossip_kernel"):
            resolve_gossip_kernel("mosaic")

    def test_algorithm_resolves_flag_strings(self):
        sched = build_schedule(RingGraph(WORLD, peers_per_itr=1))
        assert sgp(sched, GOSSIP_AXIS, gossip_kernel="xla") \
            .gossip_kernel is None
        if jax.default_backend() != "tpu":
            assert sgp(sched, GOSSIP_AXIS, gossip_kernel="auto") \
                .gossip_kernel is None
            with pytest.raises(KernelBackendError):
                sgp(sched, GOSSIP_AXIS, gossip_kernel="pallas")
        lane = KernelLane(interpret=True)
        assert sgp(sched, GOSSIP_AXIS,
                   gossip_kernel=lane).gossip_kernel is lane

    def test_overlap_keeps_the_kernel_lane(self):
        # the split start/wait kernel issues its remote DMA at launch
        # and lands it at consume, so overlap rounds ride the pallas
        # lane first-class — the old forced-xla downgrade is gone and
        # telemetry must stamp the lane that actually runs
        sched = build_schedule(RingGraph(WORLD, peers_per_itr=1))
        lane = KernelLane(interpret=True)
        sync_alg = sgp(sched, GOSSIP_AXIS, gossip_kernel=lane)
        over_alg = sgp(sched, GOSSIP_AXIS, gossip_kernel=lane,
                       overlap=True, staleness=2)
        assert sync_alg.transport_kernel_name == "pallas"
        assert over_alg.transport_kernel_name == "pallas"
        assert over_alg.gossip_kernel is lane
        assert sgp(sched, GOSSIP_AXIS).transport_kernel_name == "xla"
        assert sgp(sched, GOSSIP_AXIS,
                   overlap=True, staleness=2).transport_kernel_name \
            == "xla"

    def test_specless_codec_resolves_to_xla_lane(self):
        # a lossy codec with no in-kernel decode spec pins the XLA path
        # at _edge_transport — telemetry must stamp what actually runs,
        # not the requested lane
        class Opaque(wire.WireCodec):
            name = "opaque"
            lossy = True

        sched = build_schedule(RingGraph(WORLD, peers_per_itr=1))
        lane = KernelLane(interpret=True)
        alg = sgp(sched, GOSSIP_AXIS, gossip_kernel=lane, wire=Opaque())
        assert alg.transport_kernel_name == "xla"
        # a lossy codec WITH a spec (and the lossless exact wire, which
        # the kernel carries as the f32 passthrough) keep the lane
        assert sgp(sched, GOSSIP_AXIS, gossip_kernel=lane,
                   wire=wire.Int8Codec(64)).transport_kernel_name \
            == "pallas"


class TestDecodeSpecs:
    def test_codecs_expose_specs(self):
        assert wire.F32.kernel_spec() == wire.DecodeSpec("f32")
        assert wire.BF16.kernel_spec() == wire.DecodeSpec("bf16")
        assert wire.Int8Codec(32).kernel_spec() == \
            wire.DecodeSpec("int8", block=32)

    def test_unknown_codec_has_no_spec(self):
        class Opaque(wire.WireCodec):
            name = "opaque"
            lossy = True

        # base default: no in-kernel decode — the collective layer must
        # keep such a codec on the XLA path
        assert Opaque().kernel_spec() is None

    def test_kernel_rejects_missing_spec(self):
        with pytest.raises(ValueError, match="no in-kernel decode"):
            gossip_edge_axpy(jnp.zeros(4), (jnp.zeros(4),),
                             [1, 0], GOSSIP_AXIS, None)


# -- flag plumbing ----------------------------------------------------------


class TestFlagPlumbing:
    def test_trainer_config_default(self):
        from stochastic_gradient_push_tpu.train.loop import TrainerConfig

        # the default until ROADMAP S4 measures the lanes on the chip:
        # pallas/auto are explicit opt-ins
        assert TrainerConfig().gossip_kernel == "xla"

    def test_cli_default_and_rejection(self):
        from stochastic_gradient_push_tpu.run.gossip_sgd import (
            parse_config)

        cfg, args = parse_config(["--dataset", "synthetic"])
        assert cfg.gossip_kernel == "xla"
        if jax.default_backend() != "tpu":
            with pytest.raises(SystemExit, match="TPU backend"):
                parse_config(["--dataset", "synthetic",
                              "--gossip_kernel", "pallas"])
        cfg, _ = parse_config(["--dataset", "synthetic",
                               "--gossip_kernel", "xla"])
        assert cfg.gossip_kernel == "xla"

    def test_lm_cli_has_the_flag(self):
        from stochastic_gradient_push_tpu.run.gossip_lm import (
            build_parser)

        args = build_parser().parse_args([])
        assert args.gossip_kernel == "xla"

    def test_comm_model_stamps_the_lane(self):
        from stochastic_gradient_push_tpu.telemetry import CommModel

        sched = build_schedule(RingGraph(WORLD, peers_per_itr=1))
        d = CommModel.from_schedule(sched, 1024,
                                    gossip_kernel="pallas").to_dict()
        assert d["gossip_kernel"] == "pallas"
        # the lane re-times the wire, never re-prices it
        x = CommModel.from_schedule(sched, 1024, gossip_kernel="xla")
        p = CommModel.from_schedule(sched, 1024, gossip_kernel="pallas")
        assert x.totals(6) == p.totals(6)
        assert CommModel.from_schedule(sched, 1024).to_dict()[
            "gossip_kernel"] == "xla"


# -- chunk layout edge cases (the split path computes layouts per
# transport bucket, so every ragged shape below now also reaches the
# kernel through bucketed rounds) -------------------------------------------


class TestChunkLayout:
    def _layout(self, *a):
        from stochastic_gradient_push_tpu.ops.gossip_kernel import (
            _chunk_layout)

        return _chunk_layout(*a)

    def test_ragged_tail(self):
        # 300 elems over 128-elem chunks: 2 full + 1 ragged; the pad is
        # bounded by one chunk's tail
        assert self._layout(300, None, 128) == (128, 128, 3)

    def test_payload_smaller_than_one_chunk(self):
        # the chunk shrinks to the payload — a huge chunk target must
        # never allocate (or pad to) more than the payload itself
        # (companion of the 4 GB-pad pin in the axpy parametrization)
        assert self._layout(33, None, 1 << 30) == (33, 33, 1)

    def test_int8_block7_chunks_are_whole_blocks(self):
        # 300 elems in 7-wide blocks: 43 scale rows; a 64-elem chunk
        # target holds 9 whole blocks — scales stay chunk-local, the
        # ragged row count never splits a block across chunks
        rows, c, nb = self._layout(300, 7, 64)
        assert (rows, c, nb) == (9, 63, 5)
        assert rows * nb >= 43

    def test_payload_smaller_than_one_block(self):
        assert self._layout(3, 7, 64) == (1, 7, 1)

    def test_scalar_leaf_is_rejected(self):
        # the transport plan must route scalar leaves (the ps-weight
        # lane) to the exact-f32 ppermute — reaching the kernel with
        # one is a plan bug, not a layout to accommodate
        from stochastic_gradient_push_tpu.ops.gossip_kernel import (
            _chunk_layout)

        for bad in (0, -1):
            with pytest.raises(ValueError, match="ppermute lane"):
                _chunk_layout(bad, None, 128)

    def test_chunk_elems_validated(self):
        with pytest.raises(ValueError, match="chunk_elems"):
            self._layout(16, None, 0)


# -- the kernel itself ------------------------------------------------------


@pytest.mark.parametrize("n,chunk", [(33, 1 << 30),   # single ragged chunk
                                     (300, 128),      # 3 chunks, ragged tail
                                     (256, 64)])      # exact chunking
def test_edge_axpy_matches_ppermute_decode(n, chunk):
    """Direct kernel call vs the XLA seam it replaces, per codec, across
    chunk layouts (padding must never leak into the axpy)."""
    mesh = make_gossip_mesh(WORLD)
    dests = np.asarray([(r + 3) % WORLD for r in range(WORLD)])
    pairs = [(s, int(dests[s])) for s in range(WORLD)]
    codecs = [None, wire.BF16, wire.Int8Codec(64), wire.Int8Codec(7)]

    def f(xr):
        xr = xr.reshape(-1)
        acc = xr * 0.25
        outs = []
        for codec in codecs:
            if codec is None:
                parts, spec = (xr,), wire.F32.kernel_spec()
                ref = acc + jax.lax.ppermute(xr, GOSSIP_AXIS, pairs)
            else:
                parts, spec = codec.encode(xr), codec.kernel_spec()
                ref = acc + codec.decode(
                    tuple(jax.lax.ppermute(p, GOSSIP_AXIS, pairs)
                          for p in parts), xr)
            out = gossip_edge_axpy(acc, parts, dests, GOSSIP_AXIS, spec,
                                   interpret=True, chunk_elems=chunk)
            outs += [out[None], ref[None]]
        return tuple(outs)

    fn = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P(GOSSIP_AXIS),
                               out_specs=(P(GOSSIP_AXIS),) * 8,
                               check_vma=False))
    x = np.random.default_rng(n).normal(
        size=(WORLD, n)).astype(np.float32)
    res = [np.asarray(a) for a in jax.block_until_ready(fn(x))]
    for i, codec in enumerate(codecs):
        kern, ref = res[2 * i], res[2 * i + 1]
        name = codec.name if codec else "f32"
        if codec is None or name == "bf16":
            # pure transport (and the bf16 widen) has no arithmetic for
            # XLA to re-fuse: bit-identical
            np.testing.assert_array_equal(
                kern, ref, err_msg=f"codec {name}, n={n}, chunk={chunk}")
        else:
            # int8 dequant: XLA may fuse the reference's decode+add into
            # an FMA; the kernel's round-to-nearest product is the f32
            # tolerance the acceptance bound allows
            np.testing.assert_allclose(
                kern, ref, rtol=0, atol=1e-6,
                err_msg=f"codec {name}, n={n}, chunk={chunk}")


def test_compiled_mode_kernel_carries_the_entry_barrier():
    """The compiled (non-interpret) kernel must run the inter-device
    entry barrier before its first remote copy — signal dst AND src on
    the collective_id-keyed barrier semaphore, wait both back down.
    Mosaic lowering needs a real TPU, but the kernel body is traced at
    pallas_call time, so abstract eval catches a broken barrier (wrong
    primitive signature, mismatched SMEM spec) here: trace the
    interpret=False path and pin the barrier ops in the jaxpr.  The
    interpret path must stay barrier-free (jax's discharge rules are
    synchronous and cannot signal remote semaphores)."""
    mesh = make_gossip_mesh(WORLD)
    dests = np.asarray([(r + 1) % WORLD for r in range(WORLD)])
    codec = wire.Int8Codec(64)

    def f(interpret):
        def inner(xr):
            xr = xr.reshape(-1)
            return gossip_edge_axpy(
                xr * 0.25, codec.encode(xr), dests, GOSSIP_AXIS,
                codec.kernel_spec(), interpret=interpret,
                chunk_elems=128, collective_id=5)[None]
        return inner

    x = np.zeros((WORLD, 300), np.float32)
    traced = jax.make_jaxpr(jax.shard_map(
        f(False), mesh=mesh, in_specs=P(GOSSIP_AXIS),
        out_specs=P(GOSSIP_AXIS)))(x)
    s = str(traced)
    for op in ("get_barrier_semaphore", "semaphore_signal",
               "semaphore_wait"):
        assert op in s, f"compiled-mode kernel jaxpr lost {op}"
    interp = str(jax.make_jaxpr(jax.shard_map(
        f(True), mesh=mesh, in_specs=P(GOSSIP_AXIS),
        out_specs=P(GOSSIP_AXIS)))(x))
    assert "get_barrier_semaphore" not in interp, (
        "interpret-mode kernel must not emit the barrier (remote "
        "semaphore signals have no discharge rule)")


def test_compiled_mode_program_runs_under_the_tpu_interpreter():
    """The program the chip runs — entry barrier, depth-2 chunk pipeline
    on two semaphore slots, remote DMA of lane-dense wire rows — executed
    by the Mosaic TPU interpreter, which simulates semaphores and DMA
    across the mesh (``interpret=True`` is the HLO interpreter and runs
    the barrier-free program).  Multi-chunk, two edges folded, f32 and
    int8: bit-equal to the HLO interpreter and to numpy, no data race."""
    from jax._src.pallas.mosaic.interpret import (
        interpret_pallas_call as tpu_interpreter)
    from jax.experimental.pallas import tpu as pltpu

    from stochastic_gradient_push_tpu.ops.gossip_kernel import (
        gossip_edge_start, gossip_edge_wait)

    world, n = 4, 5000
    mesh = make_gossip_mesh(world)
    d1 = np.asarray([(r + 1) % world for r in range(world)])
    d2 = np.asarray([(r + 2) % world for r in range(world)])
    codec = wire.Int8Codec(64)
    x = np.random.default_rng(0).normal(size=(world, n)).astype(np.float32)

    def f(xr):
        xr = xr.reshape(-1)
        acc = xr * 0.25
        outs = []
        for mode in (pltpu.InterpretParams(detect_races=True), True):
            handle = gossip_edge_start(
                (jnp.stack([xr, xr * 0.5]),), np.stack([d1, d2]),
                GOSSIP_AXIS, wire.F32.kernel_spec(), n_decoded=n,
                interpret=mode, chunk_elems=512, collective_id=6)
            outs.append(gossip_edge_wait(handle, acc)[None])
            outs.append(gossip_edge_axpy(
                acc, codec.encode(xr), d1, GOSSIP_AXIS,
                codec.kernel_spec(), interpret=mode, chunk_elems=1024,
                collective_id=7)[None])
        return tuple(outs)

    fn = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P(GOSSIP_AXIS),
                               out_specs=(P(GOSSIP_AXIS),) * 4,
                               check_vma=False))
    assert "get_barrier_semaphore" in str(jax.make_jaxpr(fn)(x))
    tpu_f32, tpu_i8, hlo_f32, hlo_i8 = map(np.asarray, fn(x))
    np.testing.assert_array_equal(tpu_f32, hlo_f32)
    np.testing.assert_array_equal(tpu_i8, hlo_i8)
    np.testing.assert_array_equal(
        tpu_f32, x * 0.25 + np.roll(x, 1, axis=0)
        + 0.5 * np.roll(x, 2, axis=0))
    assert not tpu_interpreter.races.races_found


def test_dests_must_be_a_permutation():
    # the barrier handshakes with the permutation's inverse at this
    # rank, which only exists for a bijection — reject garbage early
    with pytest.raises(ValueError, match="permutation"):
        gossip_edge_axpy(jnp.zeros(4), (jnp.zeros(4),), [1, 1],
                         GOSSIP_AXIS, wire.F32.kernel_spec(),
                         interpret=True)


def _run_rounds(schedule, kernel, codec=None, ef=False, faults=None,
                thin=1, overlap=False, staleness=1, buckets=1, leaf=96):
    """ROUNDS gossip steps of one configured PushSumGossip on one
    transport lane; returns (params tree, ps-weight trajectory)."""
    alg = sgp(schedule, GOSSIP_AXIS, wire=codec, error_feedback=ef,
              faults=faults, gossip_every=thin, overlap=overlap,
              staleness=staleness, gossip_kernel=kernel,
              gossip_buckets=buckets)

    def step(p, g):
        p, g = alg.pre_step(p, g)
        return alg.post_step(p, g)

    mesh = make_gossip_mesh(WORLD)
    fn = jax.jit(jax.shard_map(step, mesh=mesh,
                               in_specs=(P(GOSSIP_AXIS),) * 2,
                               out_specs=(P(GOSSIP_AXIS),) * 2,
                               check_vma=kernel is None))
    rng = np.random.default_rng(0)
    params = {"w": rng.normal(size=(WORLD, leaf)).astype(np.float32),
              "b": rng.normal(size=(WORLD, 5)).astype(np.float32)}
    gstate = _world_stack(alg.init(
        jax.tree.map(lambda a: jnp.zeros(a.shape[1:], a.dtype), params)))
    traj = []
    for _ in range(ROUNDS):
        params, gstate = jax.block_until_ready(fn(params, gstate))
        traj.append(np.asarray(gstate.ps_weight).copy())
    return (jax.tree.map(np.asarray, params), np.stack(traj))


def test_parity_sweep_kernel_vs_xla():
    """The acceptance sweep: {f32, bf16, int8} × {EF on/off} × {plain,
    drop fault, thinning} × {sync, overlap staleness 2} × {1, 3
    transport buckets}, kernel lane vs XLA lane.  ps-weight
    trajectories bit-identical; params within f32 tolerance (FMA fusion
    on the fallback lane is the only slack).  The overlap rows now run
    the REAL kernel lane — the split start/wait transport launches its
    per-bucket remote DMA at the top of the step and lands it at the
    bottom (no forced-xla downgrade); the bucketed rows pin that the
    pipelining granularity never changes the round.

    One test on purpose: the sweep serializes its world-8 compiled
    programs (PR-8 deadlock note) and pairs each config's two lanes
    back to back.
    """
    sched = build_schedule(RingGraph(WORLD, peers_per_itr=1))
    i8 = wire.Int8Codec(64)
    # (label, codec, ef, fault, thin, overlap, buckets)
    sweep = [
        ("f32/sync", None, False, False, 1, False, 1),
        ("f32/sync/fault", None, False, True, 1, False, 1),
        ("f32/overlap2/thin", None, False, False, 2, True, 1),
        ("f32/overlap2/thin/b3", None, False, False, 2, True, 3),
        ("bf16/overlap2", wire.BF16, False, False, 1, True, 1),
        ("bf16+ef/sync/fault", wire.BF16, True, True, 1, False, 1),
        ("bf16+ef/sync/thin", wire.BF16, True, False, 2, False, 1),
        ("int8/sync", i8, False, False, 1, False, 1),
        ("int8/sync/b3", i8, False, False, 1, False, 3),
        ("int8+ef/overlap2/fault", i8, True, True, 1, True, 1),
        ("int8+ef/overlap2/fault/b3", i8, True, True, 1, True, 3),
        ("int8+ef/overlap2/thin", i8, True, False, 2, True, 1),
        ("int8+ef/sync", i8, True, False, 1, False, 1),
    ]
    for label, codec, ef, fault, thin, overlap, buckets in sweep:
        faults = (parse_fault_spec(FAULT_SPEC)
                  .build_masks(sched, gossip_every=thin)
                  if fault else None)
        kw = dict(codec=codec, ef=ef, faults=faults, thin=thin,
                  overlap=overlap, staleness=2 if overlap else 1,
                  buckets=buckets)
        p_x, w_x = _run_rounds(sched, None, **kw)
        p_k, w_k = _run_rounds(sched, KernelLane(interpret=True), **kw)
        np.testing.assert_array_equal(
            w_x, w_k,
            err_msg=f"[{label}] ps-weight trajectory must be "
                    "bit-identical across transport lanes")
        for leaf in p_x:
            d = np.abs(p_x[leaf] - p_k[leaf]).max()
            assert d <= 1e-6, (
                f"[{label}] leaf {leaf!r} diverged {d:.2e} across "
                "transport lanes (beyond f32/FMA tolerance)")


def test_hierarchical_delegate_rides_the_kernel():
    """Hierarchical rounds: the delegate (inter) edge phase takes the
    fused transport, the grouped intra-slice psum stays lax.psum — the
    two lanes must still agree."""
    sched = build_schedule(HierarchicalGraph(WORLD, slice_size=4))
    for codec, ef in [(None, False), (wire.Int8Codec(64), True)]:
        p_x, w_x = _run_rounds(sched, None, codec=codec, ef=ef)
        p_k, w_k = _run_rounds(sched, KernelLane(interpret=True),
                               codec=codec, ef=ef)
        np.testing.assert_array_equal(w_x, w_k)
        for leaf in p_x:
            assert np.abs(p_x[leaf] - p_k[leaf]).max() <= 1e-6


def test_synthesized_edge_phase_rides_the_kernel():
    """Synthesized compositions: edge phases take the fused transport,
    grouped psum phases stay exact collectives."""
    spec = {"v": 1, "world": WORLD, "phases": [
        {"kind": "edge",
         "perm": [(r + 1) % WORLD for r in range(WORLD)],
         "send": [0.5] * WORLD},
        {"kind": "psum", "group_size": 4},
    ]}
    sched = build_schedule(SynthesizedGraph(WORLD, spec=spec))
    p_x, w_x = _run_rounds(sched, None, codec=wire.Int8Codec(64),
                           ef=True)
    p_k, w_k = _run_rounds(sched, KernelLane(interpret=True),
                           codec=wire.Int8Codec(64), ef=True)
    np.testing.assert_array_equal(w_x, w_k)
    for leaf in p_x:
        assert np.abs(p_x[leaf] - p_k[leaf]).max() <= 1e-6
