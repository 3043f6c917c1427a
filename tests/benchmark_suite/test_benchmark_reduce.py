"""The yardstick's arithmetic on inputs with known answers: the trace
reduction on hand-made events, the required-operations functions against
hand counts, the peaks table."""

import pytest

from benchmark import required_ops
from benchmark import trace_reduce as tr
from benchmark.trace_reduce import Event

# one chip, two steps of 10 s each.  Step 1: a fusion 0-3, an asynchronous
# permute started at 3 (start op 3-3.5) and done 6-7 with a fusion 3.5-5
# under it, idle 5-6 and 7-10.  Step 2: a synchronous all-reduce 10-12, a
# kernel 12-14.5 inside a while 12-16, idle 16-20.
KERNEL = ('%attn.9 = (bf16[64,1024,64]{2,1,0:T(8,128)(2,1)S(1)}, '
          'f32[64,1024,1]{2,1,0:T(8,128)}) custom-call(bf16[64,1024,64]{2,1,0} '
          '%pad_maximum_fusion.30), custom_call_target="tpu_custom_call"')
DEVICE = [
    Event("fusion.1", 0.0, 3.0),
    Event("collective-permute-start.7", 3.0, 3.5),
    Event("fusion.2", 3.5, 5.0),
    Event("collective-permute-done.7", 6.0, 7.0),
    Event("all-reduce.3", 10.0, 12.0),
    Event("while.1", 12.0, 16.0),
    Event("attn.9", 12.0, 14.5, KERNEL),
]
STEPS = [Event(tr.STEP_NAME, 0.0, 10.0), Event(tr.STEP_NAME, 10.0, 20.0)]
HOST = [
    Event("bench:dispatch", 0.0, 1.0), Event("bench:fence", 1.0, 8.0),
    Event("bench:loss_fetch", 8.0, 9.0),
    Event("bench:dispatch", 10.0, 11.0), Event("bench:fence", 11.0, 19.0),
]
TRACE = tr.Trace({0: DEVICE}, HOST, STEPS)
WINDOW = (0.0, 20.0)


def test_interval_arithmetic():
    assert tr.union([(3, 5), (0, 1), (4, 7), (1, 1)]) == [(0, 1), (3, 7)]
    assert tr.clip([(0, 4), (6, 9)], 2, 7) == [(2, 4), (6, 7)]
    assert tr.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert tr.total([(0, 2), (3, 5)]) == 4


def test_busy_and_idle_are_the_union_of_operations_over_the_window():
    assert tr.window_of(TRACE) == WINDOW
    # 0-5, 6-7, 10-16: nested and overlapping operations count once
    assert tr.busy_seconds(DEVICE, WINDOW) == pytest.approx(12.0)
    assert tr.idle_gaps(DEVICE, WINDOW) == [(5.0, 6.0), (7.0, 10.0),
                                            (16.0, 20.0)]
    assert tr.busy_seconds(DEVICE, (4.5, 6.5)) == pytest.approx(1.0)


def test_collective_time_spans_start_to_done_and_exposed_is_what_nothing_hides():
    # the pair is in flight 3-7, the all-reduce 10-12
    assert tr.collective_intervals(DEVICE) == [(3.0, 7.0), (10.0, 12.0)]
    # a fusion covers 3.5-5 of the pair; the start operation itself, 5-7
    # and the all-reduce are exposed
    assert tr.exposed_collective_intervals(DEVICE) == [
        (3.0, 3.5), (5.0, 7.0), (10.0, 12.0)]
    # the trace's own line of asynchronous operations gives the same
    # stretch in one event, and counts once beside the pair
    flying = [Event("collective-permute-start.7", 3.0, 7.0),
              Event("copy-start.1", 0.0, 9.0)]
    assert tr.collective_intervals(DEVICE, flying) == \
        tr.collective_intervals(DEVICE)
    assert tr.collective_intervals([], flying) == [(3.0, 7.0)]
    # a conditional that holds the round (--gossip_every) hides nothing:
    # only what runs inside it does (the chip's w4 trace, PR 24)
    held = [Event("conditional.1", 0.0, 9.0), Event("fusion.5", 1.0, 2.0),
            Event("collective-permute-done.7", 2.0, 6.0)]
    assert tr.innermost(held) == held[1:]
    assert tr.exposed_collective_intervals(held, flying[:1]) == [(2.0, 7.0)]
    assert tr.exposed_collective_intervals(held) == [(2.0, 6.0)]


def test_start_and_done_pair_by_number_then_in_order():
    events = [Event("all-gather-start.1", 0, 1),
              Event("all-gather-start.2", 1, 2),
              Event("all-gather-done.2", 3, 4),
              Event("all-gather-done.5", 8, 9),     # number lost: oldest
              Event("reduce-scatter-start.4", 20, 21)]  # done not traced
    assert tr.collective_intervals(events) == [(0, 9), (20, 21)]
    assert not tr.is_collective(Event("fusion.collective-permute", 0, 1))


def test_kernel_time_matches_the_name_or_the_instruction():
    assert tr.matching_seconds(DEVICE, 'custom_call_target="tpu_custom_call"',
                               WINDOW) == pytest.approx(2.5)
    assert tr.matching_seconds(DEVICE, r"^fusion", WINDOW) == \
        pytest.approx(4.5)
    assert tr.matching_seconds(DEVICE, r"no_such_kernel", WINDOW) == 0.0


def test_self_time_takes_a_nested_operation_out_of_its_parent():
    by_name = tr.self_seconds(DEVICE)
    assert by_name["while.1"] == pytest.approx(1.5)
    kernel = "attn.9 custom-call bf16[64,1024,64]"
    assert by_name[kernel] == pytest.approx(2.5)
    assert tr.top(by_name, 2) == [["fusion.1", 3.0], [kernel, 2.5]]


def test_back_to_back_operations_1e_17_s_apart_are_not_nested():
    """0.1 + 0.2 reads 5.6e-17 s after 0.3, where the next operation
    starts: on whole picoseconds the two follow each other, each keeps its
    own time, both are innermost, and the parts add up to the busy time."""
    events = [Event("fusion.1", 0.1, 0.1 + 0.2), Event("fusion.2", 0.3, 0.5),
              Event("while.3", 0.5, 0.9), Event("fusion.4", 0.5, 0.5 + 0.4)]
    assert 0 < events[0].end - events[1].start < 1e-16      # the hair
    got = tr.self_seconds(events)
    assert got["fusion.1"] == pytest.approx(0.2)
    assert got["fusion.2"] == pytest.approx(0.2)
    # a body that ends with its while, to the picosecond, is still inside it
    assert got["while.3"] == pytest.approx(0.0, abs=1e-12)
    assert got["fusion.4"] == pytest.approx(0.4)
    assert sum(got.values()) == pytest.approx(
        tr.busy_seconds(events, (0.0, 1.0)), abs=1e-12)
    assert [e.name for e in tr.innermost(events)] == [
        "fusion.1", "fusion.2", "fusion.4"]
    # keyed as the caller asks: the scope readers key by instruction
    assert tr.self_seconds(events[:2], key=lambda e: "all") == {
        "all": pytest.approx(0.4)}


def test_the_chips_instruction_text_gives_a_name_and_a_label():
    fusion = ("%fusion.13 = (f32[256]{0:T(256)S(1)}, /*index=5*/bf16[256,56,"
              "56,256]{3,0,2,1:T(8,128)(2,1)}) fusion(f32[256]{0:T(256)S(1)} "
              "%copy-done.368), kind=kOutput, calls=%fused_computation.1")
    assert tr.short_name(fusion) == "fusion.13"
    assert tr.label(fusion) == "fusion.13 fusion:Output bf16[256,56,56,256]"
    permute = ("%collective-permute-start.7 = (f32[4]{0}, f32[4]{0}) "
               "collective-permute-start(f32[4]{0} %x), channel_id=3")
    assert tr.is_collective(Event(tr.short_name(permute), 0, 1))
    assert tr.label("bench:fence") == tr.short_name("bench:fence") == \
        "bench:fence"


def test_idle_gaps_go_to_the_host_annotation_they_fell_in():
    gaps = tr.idle_gaps(DEVICE, WINDOW)
    by_host = tr.attribute_gaps(gaps, HOST)
    # 5-6 and 7-8 under the first fence, 8-9 the loss fetch, 9-10 nothing;
    # 16-19 under the second fence, 19-20 nothing
    assert by_host == {"bench:fence": pytest.approx(5.0),
                       "bench:loss_fetch": pytest.approx(1.0),
                       "bench:between": pytest.approx(2.0)}
    assert sum(by_host.values()) == pytest.approx(tr.total(gaps))


def test_mean_over_devices_and_no_device():
    two = tr.Trace({0: DEVICE, 1: DEVICE[:1]}, HOST, STEPS,
                   {1: [Event("copy-start.1", 0.0, 9.0)]})
    assert tr.mean_over_devices(
        two, lambda ev, _: tr.busy_seconds(ev, WINDOW)) == pytest.approx(7.5)
    assert tr.mean_over_devices(
        two, lambda _, flying: float(len(flying))) == pytest.approx(0.5)
    assert tr.mean_over_devices(tr.Trace({}, HOST, STEPS), len) is None


def test_resnet50_required_operations_match_the_hand_count():
    # stem 7x7x3x64 at 112^2, ..., classifier 2048x1000: torchvision's
    # 4.09 GMAC an image forward
    assert required_ops.resnet_forward_macs(50, 224, 1000) == 4_089_184_256
    stem = 112 * 112 * 7 * 7 * 3 * 64
    assert required_ops.resnet_forward_macs(50, 224, 1000) - \
        required_ops.resnet_forward_macs(50, 224, 0) == 2048 * 1000
    assert stem == 118_013_952
    assert required_ops.resnet_train_flops(
        2, depth=50, image_size=224, num_classes=1000) == \
        pytest.approx(2 * 6 * 4_089_184_256)


def test_lm_and_flash_required_operations_match_the_hand_count():
    # one layer, d 8, ff 16, vocab 10, t 4: projections 2*4*(4*64+2*128),
    # attention 2 products over 10 causal pairs, head 2*4*8*10
    shape = dict(n_layers=1, d_model=8, d_ff=16, vocab=10, seq_len=4)
    assert required_ops.lm_forward_flops_per_sequence(**shape) == \
        2 * 4 * (4 * 64 + 2 * 128) + 2 * 2 * 10 * 8 + 2 * 4 * 8 * 10
    assert required_ops.lm_train_flops(3, **shape) == \
        9 * required_ops.lm_forward_flops_per_sequence(**shape)
    flops = required_ops.flash_flops(batch=2, heads=3, seq_len=4,
                                     head_dim=8)
    one_product = 2 * 2 * 3 * 10 * 8
    assert flops == {"forward": 2 * one_product,
                     "backward": 5 * one_product}
    assert required_ops.flash_flops(batch=2, heads=3, seq_len=4, head_dim=8,
                                    causal=False)["forward"] == \
        2 * 2 * 2 * 3 * 16 * 8
    assert required_ops.flash_bytes(batch=2, heads=3, seq_len=4,
                                    head_dim=8) == \
        {"forward": 4 * 384.0, "backward": 8 * 384.0}


def test_peaks_are_the_published_ones_and_an_unknown_chip_is_an_error():
    v5e = required_ops.peaks("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    # GPT-2 medium's attention at t1024, head 64 is compute-bound there
    at = dict(batch=4, heads=16, seq_len=1024, head_dim=64)
    assert required_ops.roofline_seconds(
        sum(required_ops.flash_flops(**at).values()),
        sum(required_ops.flash_bytes(**at).values()), v5e)["bound"] == \
        "compute"
    assert required_ops.roofline_seconds(1.0, 1e6, v5e)["bound"] == "memory"
    for kind in ("TPU v9", "cpu", "_source"):
        with pytest.raises(KeyError, match="no row"):
            required_ops.peaks(kind)


def test_plain_references_agree_with_the_programs_models_in_float32():
    import jax
    import jax.numpy as jnp

    from benchmark.reference import lm as plain_lm, resnet as plain_resnet
    from stochastic_gradient_push_tpu.models.resnet import (
        Bottleneck, ResNet)
    from stochastic_gradient_push_tpu.models.transformer import (
        TransformerConfig, TransformerLM)

    def jitter(tree):      # so that zero biases and unit scales matter
        return jax.jit(lambda t: jax.tree.map(
            lambda a: a + 0.05 * jax.random.normal(
                jax.random.PRNGKey(3), a.shape), t))(tree)

    lm = TransformerLM(TransformerConfig(
        vocab_size=97, d_model=32, n_layers=2, n_heads=4, d_ff=64,
        max_len=16, attn_impl="full"))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 97)
    params = jitter(jax.jit(lm.init)(jax.random.PRNGKey(0),
                                     tokens)["params"])
    ours = lm.apply({"params": params}, tokens)
    theirs = plain_lm.lm_logits(params, tokens, 4)
    assert float(jnp.abs(ours - theirs).max()) < 1e-4
    assert float(plain_lm.lm_loss(theirs, tokens)) == pytest.approx(
        float(-jnp.take_along_axis(jax.nn.log_softmax(ours), tokens[
            ..., None], -1).mean()), abs=1e-5)

    # ResNet-50's blocks, one a stage: stem, pool, strides, projections
    net = ResNet(stage_sizes=[1, 1, 1, 1], block_cls=Bottleneck,
                 num_classes=10)
    images = jax.random.normal(jax.random.PRNGKey(2), (4, 32, 32, 3))
    variables = jax.jit(lambda key, x: net.init(key, x, train=True))(
        jax.random.PRNGKey(0), images)
    params = jitter(variables["params"])
    ours, _ = net.apply(
        {"params": params, "batch_stats": variables["batch_stats"]},
        images, train=True, mutable=["batch_stats"])
    theirs = jax.jit(plain_resnet.resnet_logits, static_argnums=2)(
        params, images, (1, 1, 1, 1))
    assert float(jnp.abs(ours - theirs).max()) < 1e-3 * float(
        jnp.abs(theirs).max())


@pytest.mark.parametrize("seq_len,q_block", [(64, 16), (64, 32), (48, 16)])
def test_blocked_reference_attention_is_the_whole_forms_rows(seq_len, q_block):
    """The plain LM's attention in blocks of query rows (what lets its
    [16, 8192, 8192] scores fit a chip) against all rows at once: the same
    rows, to float32 rounding, in the logits and in the loss."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import lm as plain

    keys = jax.random.split(jax.random.PRNGKey(5), 12)
    dense = lambda k, i, o: {"kernel": jax.random.normal(k, (i, o)) * i ** -0.5}
    norm = lambda k: {"scale": 1 + 0.1 * jax.random.normal(k, (32,)),
                      "bias": 0.1 * jax.random.normal(k, (32,))}
    block = lambda ks: {
        "ln1": norm(ks[0]), "ln2": norm(ks[1]),
        "attn": {n: dense(k, 32, 32) for n, k in zip("qkvo", ks[2:6])},
        "up": {**dense(ks[6], 32, 64), "bias": jnp.zeros(64)},
        "down": {**dense(ks[7], 64, 32), "bias": jnp.zeros(32)}}
    params = {"embed": {"embedding": jax.random.normal(keys[8], (97, 32))},
              "block_0": block(keys), "block_1": block(keys[::-1]),
              "ln_f": norm(keys[9]), "lm_head": dense(keys[10], 32, 97)}
    tokens = jax.random.randint(keys[11], (2, seq_len), 0, 97)
    whole = plain.lm_logits(params, tokens, 4, q_block=None)
    blocked = jax.jit(plain.lm_logits, static_argnums=(2, 3))(
        params, tokens, 4, q_block)
    assert whole.shape == blocked.shape == (2, seq_len, 97)
    assert float(jnp.abs(whole - blocked).max()) <= 2e-6 * float(
        jnp.abs(whole).max())
    assert float(plain.lm_loss(blocked, tokens)) == pytest.approx(
        float(plain.lm_loss(whole, tokens)), abs=1e-6)
    # the blocks are taken where they divide the sequence, and a sequence
    # of the t1024 cell's length is one block: the form PR 24 accepted
    assert plain.Q_BLOCK == 1024


def test_compare_reads_the_largest_gap_and_the_loss_gap_in_one_program():
    import jax.numpy as jnp

    from benchmark.reference import compare, lm as plain

    theirs = jnp.asarray([[[2.0, -4.0, 1.0], [0.5, 0.0, -1.0]]])
    ours = theirs.at[0, 1, 2].add(0.2)
    targets = jnp.asarray([[0, 1]])
    tolerance = {"logit_tolerance": 0.06, "loss_tolerance": 0.05}
    got = compare.compare(ours, theirs, plain.lm_loss, targets, tolerance)
    assert got["logit_error"] == pytest.approx(0.2 / 4.0)
    assert got["loss_error"] == pytest.approx(abs(
        float(plain.lm_loss(ours, targets))
        - float(plain.lm_loss(theirs, targets))))
    assert got["ok"] is True and got["logit_tolerance"] == 0.06
    tight = {"logit_tolerance": 0.04, "loss_tolerance": 0.05}
    assert compare.compare(ours, theirs, plain.lm_loss, targets,
                           tight)["ok"] is False


def test_gpt2_mediums_attention_at_t8192_is_a_third_of_the_step():
    """The t8192 cell's reason: attention's required operations against
    the step's, and a roofline that is compute-bound."""
    shape = dict(n_layers=24, d_model=1024, d_ff=4096, vocab=50257,
                 seq_len=8192)
    step = required_ops.lm_train_flops(1, **shape)
    assert step == pytest.approx(27.3e12, rel=0.01)
    at = dict(batch=1, heads=16, seq_len=8192, head_dim=64)
    flash = required_ops.flash_flops(**at)
    # 7 products where the step's count has 6 (the backward's recomputed
    # scores are the kernel's requirement, not the mathematics')
    assert 24 * sum(flash.values()) * 6 / 7 / step == pytest.approx(
        0.36, abs=0.01)
    assert required_ops.roofline_seconds(
        sum(flash.values()), sum(required_ops.flash_bytes(**at).values()),
        required_ops.peaks("TPU v5 lite"))["bound"] == "compute"


@pytest.mark.parametrize("precision,least,most", [
    ("fp32", 3e-3, 0.1), ("bf16", 0.05, 1.0)])
def test_the_resnet_references_control_rounds_every_product(precision, least,
                                                            most):
    """``operand`` reaches the stem, every block's convolutions, the
    projection and the classifier: the control one precision down departs
    from the reference by that precision's rounding, no less and not
    wildly more."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import compare, resnet as plain
    from stochastic_gradient_push_tpu.models.resnet import Bottleneck, ResNet

    net = ResNet(stage_sizes=[1, 1, 1, 1], block_cls=Bottleneck,
                 num_classes=10)
    images = jax.random.normal(jax.random.PRNGKey(2), (4, 32, 32, 3))
    params = jax.jit(lambda key, x: net.init(key, x, train=True))(
        jax.random.PRNGKey(0), images)["params"]
    params = jax.jit(lambda t: jax.tree.map(       # no zero scales
        lambda a: a + 0.05 * jax.random.normal(
            jax.random.PRNGKey(3), a.shape), t))(params)
    logits = jax.jit(plain.resnet_logits, static_argnums=(2, 3))
    theirs = logits(params, images, (1, 1, 1, 1), plain._same)
    control = logits(params, images, (1, 1, 1, 1),
                     compare.rounded_to(precision))
    gap = float(jnp.abs(control - theirs).max() / jnp.abs(theirs).max())
    assert least < gap < most
