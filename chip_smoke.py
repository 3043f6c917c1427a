"""The quickest proof that the trainers still start on the chip.

    python chip_smoke.py             # one chip: ResNet-50 SGP, dense LM,
                                     # the experts' grouped kernels
    python chip_smoke.py --chips 4   # four chips: SGP vs AR, placement,
                                     # the Pallas gossip lane

One process drives the program's normal entry points
(``run.gossip_sgd.main`` / ``run.gossip_lm.main``) at full width, with
random weights and synthetic data made from a seed, and checks what comes
out by the repo's own means: the per-rank CSVs, the typed health events,
the checkpoints, the compiled program's text.  There is no CPU branch: a
backend that is not a TPU ends the script non-zero with ``"ok": false``.

Each phase prints one JSON line of observations; the last line of standard
output is the verdict, ``{"ok": true, "device": {"platform": ..., "kind":
..., "count": ...}}``.  Times here include compilation: they are cold
observations, never benchmark numbers.
"""

from __future__ import annotations

import argparse
import csv
import faulthandler
import functools
import json
import math
import os
import shutil
import sys
import time
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))
# the script's contract: done, compilation included, within 1200 s
DEADLINE_S = 1150
# run directories (checkpoints are hundreds of MB: not under chiprun_out/)
OUT_DIR = os.path.join(HERE, ".chip_smoke")

# Every size the smoke runs at.  Tests call the phase functions with these
# overridden to toy values; the script itself always uses this table.
RESNET = {
    "model": "resnet50", "image_size": 224, "num_classes": 1000,
    "batch_size": 128, "precision": "bf16",
    # 2 epochs x 4 iterations: an epoch boundary, validation and a
    # checkpoint are on the path.  Four batches a rank keep the float64
    # synthetic set (data/pipeline.py) under a GB a rank on the host
    "epochs": 2, "iters_per_epoch": 4,
}
SIZES = {
    "resnet50_sgp": dict(RESNET, world_size=1),
    "lm_dense_flash": {
        "d_model": 768, "n_layers": 12, "n_heads": 12, "d_ff": 3072,
        "seq_len": 1024, "batch_size": 8, "vocab_size": 32768,
        "precision": "bf16", "world_size": 1, "num_steps": 6,
        # the auto rule's answer on a TPU, and the shape at which the
        # kernel must be IN the compiled program (None skips the check)
        "attn": "flash", "kernel_shape": (8, 12, 1024, 64),
    },
    # the top-k expert layer's products at the published sizes (8192 tokens
    # send 32768 pairs to 16 held experts; 2048 to gate | up of 3584)
    "grouped_kernels": {
        "rows": 32768, "width": 2048, "out": 3584, "experts": 16,
        "dtype": "bfloat16", "interpret": False,
        # both sides round a float32 sum to bf16; the sums' order differs
        "tolerance": 2.0 ** -6,
    },
    # the four-chip phases share one configuration: SGP, what the paper
    # compares it with (AR), and SGP again on the Pallas transport
    "w4": dict(RESNET, world_size=4, health_every=2,
               gossip_kernel="pallas",
               # one gossip round's flat payload: ResNet-50's parameters
               payload_elems=25_557_032),
}


def say(line: dict) -> None:
    print(json.dumps(line), flush=True)


def cache_entries(cache_dir: str) -> int:
    return len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0


def observe(name: str, t0: float, steps: int, losses, cache_dir: str,
            **more) -> dict:
    """One phase's line: observations, not metrics."""
    import jax

    dev = jax.devices()[0]
    stats = dev.memory_stats() or {}
    seconds = time.time() - t0
    return {
        "phase": name, "ok": True, "seconds": round(seconds, 1),
        "steps": steps,
        "cold_seconds_per_step": round(seconds / max(steps, 1), 2),
        "note": "cold: compile and set-up included, not a benchmark",
        "first_loss": losses[0], "last_loss": losses[-1],
        "device_kind": dev.device_kind,
        "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
        "compile_cache_dir": cache_dir,
        "compile_cache_entries": cache_entries(cache_dir),
        **more,
    }


def fresh_dir(name: str) -> str:
    """An empty run directory: the CSVs append and a stale checkpoint
    would be somebody else's answer."""
    path = os.path.join(OUT_DIR, name)
    shutil.rmtree(path, ignore_errors=True)
    os.makedirs(path)
    return path


# -- the image trainer ------------------------------------------------------


def sgd_argv(sizes: dict, out: str, extra=()) -> list[str]:
    """The ``gossip_sgd`` command line for one configuration."""
    world = sizes["world_size"]
    samples = world * sizes["batch_size"] * sizes["iters_per_epoch"]
    return [
        "--model", sizes["model"], "--dataset", "synthetic",
        "--image_size", str(sizes["image_size"]),
        "--num_classes", str(sizes["num_classes"]),
        "--batch_size", str(sizes["batch_size"]),
        "--precision", sizes["precision"],
        "--world_size", str(world),
        "--num_epochs", str(sizes["epochs"]),
        "--synthetic_samples", str(samples),
        "--print_freq", "1", "--checkpoint_dir", out,
        *extra,
    ]


def run_sgd(sizes: dict, out: str, extra=(), gossip_kernel=None) -> dict:
    """``gossip_sgd.main`` in this process; returns its result.  A string
    ``gossip_kernel`` is the CLI flag; a lane object (tests: the kernel
    in interpret mode) goes through main's ``config_transform`` hook."""
    from stochastic_gradient_push_tpu.run import gossip_sgd

    argv = sgd_argv(sizes, out, extra)
    transform = None
    if isinstance(gossip_kernel, str):
        argv += ["--gossip_kernel", gossip_kernel]
    elif gossip_kernel is not None:
        def transform(cfg, args):
            cfg.gossip_kernel = gossip_kernel
            return cfg
    return gossip_sgd.main(argv, config_transform=transform)


def sgd_losses(out: str, world: int) -> list[float]:
    """Per-step training losses from the trainer's rank-averaged CSV
    (the epoch's last row is written twice: count steps, not rows)."""
    steps = {}
    with open(os.path.join(out, f"out_r0_n{world}.csv")) as f:
        rows = list(csv.reader(f))
    header = next(i for i, r in enumerate(rows) if r and r[0] == "Epoch")
    loss_col = rows[header].index("Loss")
    for r in rows[header + 1:]:
        if int(r[1]) >= 0:  # itr -1 is the validation row
            steps[(int(r[0]), int(r[1]))] = float(r[loss_col])
    return [steps[k] for k in sorted(steps)]


def check_losses(losses, want_steps: int, what: str) -> None:
    if len(losses) != want_steps:
        raise RuntimeError(
            f"{what}: asked for {want_steps} steps, the CSV holds "
            f"{len(losses)}")
    if not all(math.isfinite(x) for x in losses):
        raise RuntimeError(f"{what}: non-finite loss in {losses}")


def resnet50_sgp(sizes: dict, cache_dir: str, name="resnet50_sgp",
                 extra=(), gossip_kernel=None) -> dict:
    t0 = time.time()
    out = fresh_dir(name)
    result = run_sgd(sizes, out, extra, gossip_kernel)
    losses = sgd_losses(out, sizes["world_size"])
    check_losses(losses, sizes["epochs"] * sizes["iters_per_epoch"], name)
    if not math.isfinite(result["final_prec1"]):
        raise RuntimeError(f"{name}: validation returned "
                           f"{result['final_prec1']}")
    return observe(name, t0, len(losses), losses, cache_dir,
                   val_prec1=result["final_prec1"], run_dir=out)


# -- the LM trainer ---------------------------------------------------------


def kernel_in_program(shape) -> int:
    """Compile flash attention forward+backward at ``shape`` on the
    device and count the Mosaic custom calls in the program: a silent
    route to the blockwise reference would leave none."""
    import jax
    import jax.numpy as jnp

    from stochastic_gradient_push_tpu.ops.flash_attention import (
        flash_attention)

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True).astype(
            jnp.float32).sum()

    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        x, x, x).compile().as_text()
    calls = text.count("tpu_custom_call")
    if calls == 0:
        raise RuntimeError(
            f"flash_attention at {shape} compiled with no tpu_custom_call: "
            "the kernel is not in the program")
    return calls


def lm_dense_flash(sizes: dict, cache_dir: str) -> dict:
    from stochastic_gradient_push_tpu.run import gossip_lm

    t0 = time.time()
    out = fresh_dir("lm_dense_flash")
    # --attn is NOT given: the auto rule is what is exercised
    result = gossip_lm.main([
        "--d_model", str(sizes["d_model"]),
        "--n_layers", str(sizes["n_layers"]),
        "--n_heads", str(sizes["n_heads"]), "--d_ff", str(sizes["d_ff"]),
        "--seq_len", str(sizes["seq_len"]),
        "--batch_size", str(sizes["batch_size"]),
        "--vocab_size", str(sizes["vocab_size"]),
        "--precision", sizes["precision"],
        "--world_size", str(sizes["world_size"]),
        "--num_steps", str(sizes["num_steps"]), "--print_freq", "1",
        "--checkpoint_dir", out,
    ])
    if result["attn"] != sizes["attn"]:
        raise RuntimeError(
            f"lm_dense_flash: the auto rule resolved attention to "
            f"{result['attn']!r}, expected {sizes['attn']!r}")
    with open(os.path.join(
            out, f"lm_out_n{sizes['world_size']}.csv")) as f:
        losses = [float(r["loss"]) for r in csv.DictReader(f)]
    check_losses(losses, sizes["num_steps"], "lm_dense_flash")
    calls = (kernel_in_program(sizes["kernel_shape"])
             if sizes["kernel_shape"] else None)
    return observe("lm_dense_flash", t0, len(losses), losses, cache_dir,
                   attn=result["attn"], flash_custom_calls=calls,
                   run_dir=out)


def grouped_splits(rows: int, experts: int, tile: int) -> dict:
    """Splits a router can give the held experts: half of the rows evenly,
    an uneven draw with groups that share tiles, the held rows ending on a
    tile's boundary with empty experts behind them (an empty expert's one
    visit then lands on a tile no product wrote), and every row held."""
    import numpy as np

    rng = np.random.default_rng(0)
    half, some = rows // 2, max(experts // 3, 1)
    return {
        "even": np.full(experts, half // experts),
        "uneven": rng.multinomial(
            half - tile // 3, rng.dirichlet(np.full(experts, 0.5))),
        "trailing_empty_on_a_boundary": np.r_[
            rng.multinomial(half // tile * tile, np.full(some, 1 / some)),
            np.zeros(experts - some, np.int64)],
        "every_row": rng.multinomial(rows, np.full(experts, 1 / experts)),
    }


def grouped_kernels(sizes: dict, cache_dir: str) -> dict:
    """The expert layer's three kernels (``ops/grouped_matmul.py``),
    compiled, against ``lax.ragged_dot`` at the published sizes: the
    output, the rows' gradient and the blocks' gradient over
    ``grouped_splits``, with NaN in the rows and in the output's gradient
    past the held rows (what no product wrote is whatever was in memory).
    The benchmark's ``correct`` runs the layer forward only; this is what
    holds the two backward kernels on the chip."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    from stochastic_gradient_push_tpu.ops import grouped_matmul as gm

    t0 = time.time()
    m, k, n, g = (sizes[key] for key in ("rows", "width", "out", "experts"))
    dtype, interpret = jnp.dtype(sizes["dtype"]), sizes["interpret"]
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(2), 3)
    x = jax.random.normal(k1, (m, k), dtype)
    w = (jax.random.normal(k2, (g, k, n)) * k ** -0.5).astype(dtype)
    d_out = jax.random.normal(k3, (m, n), dtype)
    if not interpret and not gm.kernel_fits(jax.default_backend(), x, w):
        raise RuntimeError(
            f"grouped_kernels: kernel_fits refuses {x.shape} by {w.shape} "
            f"on {jax.default_backend()}: the layer would take ragged_dot")

    @functools.partial(jax.jit, static_argnames="kernels")
    def products(x, w, d_out, split, kernels):
        held = (jnp.arange(m) < split.sum())[:, None]
        clean = lambda a: jnp.where(held, a, 0)
        dot = (lambda x, w: gm.grouped_matmul(x, w, split, interpret)) \
            if kernels else (lambda x, w: lax.ragged_dot(x, w, split))
        fill = jnp.nan if kernels else 0
        out, vjp = jax.vjp(dot, jnp.where(held, x, fill), w)
        d_rows, d_blocks = vjp(jnp.where(held, d_out, fill))
        return clean(out), clean(d_rows), d_blocks

    worst = {}
    for name, split in grouped_splits(m, g, gm.ROW_TILE).items():
        split = jnp.asarray(split, jnp.int32)
        pairs = zip(("out", "d_rows", "d_blocks"),
                    products(x, w, d_out, split, True),
                    products(x, w, d_out, split, False))
        for what, got, want in pairs:
            got, want = (np.asarray(t, np.float32) for t in (got, want))
            error = float(np.abs(got - want).max() / np.abs(want).max())
            if not error <= sizes["tolerance"]:     # NaN fails too
                raise RuntimeError(
                    f"grouped_kernels: {what} over the split {name!r} is "
                    f"{error} of the largest value from lax.ragged_dot's "
                    f"(tolerance {sizes['tolerance']})")
            worst[what] = max(worst.get(what, 0.0), error)
    return {"phase": "grouped_kernels", "ok": True,
            "seconds": round(time.time() - t0, 1),
            "worst_share_of_largest": worst,
            "compile_cache_dir": cache_dir,
            "compile_cache_entries": cache_entries(cache_dir)}


# -- four chips -------------------------------------------------------------


def check_placement(state, world: int) -> dict:
    """Every rank's row of the train state lives on its own device: a
    parameter leaf and the push-sum weight each spread over ``world``
    distinct devices, and each of them holds real bytes."""
    import jax

    leaves = jax.tree.leaves(state.params)
    leaf = leaves[0]
    rank_bytes = sum(a.nbytes for a in leaves) // world
    found = {}
    for what, arr in (("params", leaf),
                      ("ps_weight", state.gossip.ps_weight)):
        devices = {s.device for s in arr.addressable_shards}
        if len(devices) != world:
            raise RuntimeError(
                f"placement: {what} lives on {len(devices)} device(s) "
                f"{sorted(str(d) for d in devices)}, expected {world}")
        found[what] = sorted(d.id for d in devices)
    in_use = {}
    for d in {s.device for s in leaf.addressable_shards}:
        stats = d.memory_stats()
        if stats is not None:  # the CPU backend keeps no such count
            in_use[d.id] = stats["bytes_in_use"]
            if stats["bytes_in_use"] < rank_bytes:
                raise RuntimeError(
                    f"placement: device {d.id} holds "
                    f"{stats['bytes_in_use']} bytes, less than one "
                    f"rank's parameters ({rank_bytes})")
    return {"devices": found, "bytes_in_use": in_use}


def placement_w4(sizes: dict, cache_dir: str) -> dict:
    """Build the Trainer the way ``gossip_sgd.main`` does, take
    ``init_state`` through one real step, and look at where things are
    and at what the step compiled to."""
    import jax
    import jax.numpy as jnp

    from stochastic_gradient_push_tpu.data import (
        DistributedSampler, ShardedLoader, synthetic_classification)
    from stochastic_gradient_push_tpu.models import RESNETS, TinyCNN
    from stochastic_gradient_push_tpu.parallel import make_gossip_mesh
    from stochastic_gradient_push_tpu.run import gossip_sgd
    from stochastic_gradient_push_tpu.train.loop import Trainer

    t0 = time.time()
    world, batch = sizes["world_size"], sizes["batch_size"]
    # one epoch of sgp_w4's own configuration, so that the step compiled
    # here is the one sgp_w4 finds in the compile cache
    cfg, args = gossip_sgd.parse_config(sgd_argv(
        dict(sizes, epochs=1), fresh_dir("placement_w4"),
        ["--train_fast", "True",
         "--health_every", str(sizes["health_every"])]))
    dtype = jnp.bfloat16 if args.precision == "bf16" else jnp.float32
    model = {**RESNETS, "tiny_cnn": TinyCNN}[args.model](
        num_classes=cfg.num_classes, dtype=dtype)
    shape = (batch, args.image_size, args.image_size, 3)
    trainer = Trainer(cfg, model, make_gossip_mesh(world),
                      sample_input_shape=shape)
    images, labels = synthetic_classification(
        args.synthetic_samples, num_classes=cfg.num_classes,
        image_size=args.image_size, seed=cfg.seed)
    sampler = DistributedSampler(len(images), world)
    state, _ = trainer.fit(
        trainer.init_state(),
        ShardedLoader(images, labels, batch, sampler), sampler)
    jax.block_until_ready(state)
    where = check_placement(state, world)
    # the step fit() just ran (memoized under the same key)
    _, step = trainer._train_fn(1, sizes["iters_per_epoch"])
    text = step.lower(
        state, jax.ShapeDtypeStruct((world,) + shape, jnp.float32),
        jax.ShapeDtypeStruct((world, batch), jnp.int32)
    ).compile().as_text()
    permutes = text.count("collective-permute")
    if permutes == 0:
        raise RuntimeError("placement: the compiled SGP step holds no "
                           "collective-permute")
    return {"phase": "placement_w4", "ok": True,
            "seconds": round(time.time() - t0, 1),
            "collective_permutes": permutes, **where,
            "compile_cache_entries": cache_entries(cache_dir)}


def health_of(trace_dir: str) -> list[dict]:
    with open(os.path.join(trace_dir, "events.jsonl")) as f:
        events = [json.loads(line) for line in f]
    return [e["data"] for e in events if e["kind"] == "health"]


def sgp_w4(sizes: dict, cache_dir: str, name="sgp_w4",
           gossip_kernel=None) -> dict:
    """SGP over four ranks with the health line on: push-sum mass is
    conserved and, on this regular graph, every weight stays 1."""
    trace = os.path.join(OUT_DIR, name, "trace")
    line = resnet50_sgp(
        sizes, cache_dir, name,
        ["--health_every", str(sizes["health_every"]),
         "--trace_dir", trace], gossip_kernel)
    health = health_of(trace)
    if not health:
        raise RuntimeError(f"{name}: no gossip health event was emitted")
    for h in health:
        if not (h["ps_mass_err"] <= 1e-6
                and abs(h["ps_w_min"] - 1.0) <= 1e-6
                and abs(h["ps_w_max"] - 1.0) <= 1e-6
                and h["nonfinite_params"] == 0.0):
            raise RuntimeError(f"{name}: unhealthy gossip: {h}")
    last = health[-1]
    return dict(line, health_events=len(health),
                ps_mass_err=last["ps_mass_err"], ps_w_min=last["ps_w_min"],
                ps_w_max=last["ps_w_max"],
                consensus_residual=last["consensus_residual"])


def ar_w4(sizes: dict, cache_dir: str) -> dict:
    return resnet50_sgp(sizes, cache_dir, "ar_w4",
                        ["--all_reduce", "True", "--graph_type", "-1"])


def checkpoint_params(run_dir: str, world: int) -> dict:
    import flax.serialization
    from flax.traverse_util import flatten_dict

    path = os.path.join(run_dir, f"checkpoint_r0_n{world}.ckpt")
    with open(path, "rb") as f:
        raw = flax.serialization.msgpack_restore(f.read())
    return flatten_dict(raw["state"]["params"], sep="/")


def pallas_round_vs_xla(sizes: dict) -> dict:
    """One push-sum round per schedule phase on both transport lanes,
    same payload, f32 and int8 wire: the kernel lane against the
    ``ppermute`` lane with no training dynamics in between.  Returns the
    largest absolute difference per wire (values are unit normals)."""
    import jax
    import numpy as np
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from stochastic_gradient_push_tpu.ops.gossip_kernel import (
        resolve_gossip_kernel)
    from stochastic_gradient_push_tpu.parallel import (
        GOSSIP_AXIS, collectives, make_gossip_mesh, wire)
    from stochastic_gradient_push_tpu.topology import (
        NPeerDynamicDirectedExponentialGraph, build_schedule)

    world = sizes["world_size"]
    lane = sizes["gossip_kernel"]
    if isinstance(lane, str):
        lane = resolve_gossip_kernel(lane)
    mesh = make_gossip_mesh(world)
    sched = build_schedule(
        NPeerDynamicDirectedExponentialGraph(world, peers_per_itr=1))
    rank_major = NamedSharding(mesh, P(GOSSIP_AXIS))
    x = jax.device_put(
        np.random.default_rng(0).standard_normal(
            (world, sizes["payload_elems"]), np.float32), rank_major)
    w = jax.device_put(np.ones((world,), np.float32), rank_major)

    def rounds(kernel, codec):
        def per_rank(p, ps, phase):
            p2, w2 = collectives.mix_push_sum(
                p[0], ps[0], phase[0], sched, GOSSIP_AXIS, codec=codec,
                kernel=kernel)
            return p2[None], w2[None]

        # an interpreted lane (tests) cannot run under the vma check
        fn = jax.jit(jax.shard_map(
            per_rank, mesh=mesh, in_specs=P(GOSSIP_AXIS),
            out_specs=P(GOSSIP_AXIS),
            check_vma=kernel is None or not kernel.interpret))
        return [jax.device_get(fn(x, w, jax.device_put(
            np.full((world,), phase, np.int32), rank_major)))
            for phase in range(sched.num_phases)]

    worst = {}
    for name, codec in (("f32", None), ("int8", wire.Int8Codec(64))):
        diffs = []
        for (p_x, w_x), (p_k, w_k) in zip(rounds(None, codec),
                                          rounds(lane, codec)):
            if not np.array_equal(w_x, w_k):
                raise RuntimeError(
                    f"pallas round ({name}): push-sum weights differ "
                    f"between lanes: {w_x} vs {w_k}")
            diffs.append(float(np.max(np.abs(p_x - p_k))))
        worst[name] = max(diffs)
    return worst


# one rounding of the receive axpy (XLA may fuse it into an FMA) on unit
# normals
ROUND_TOLERANCE = 1e-5


def sgp_w4_pallas(sizes: dict, cache_dir: str) -> dict:
    """The Pallas gossip transport on four chips: first one round per
    phase against the XLA lane on the same payload, then ``sgp_w4``
    again, flag for flag, on the kernel lane, compared leaf by leaf
    with the XLA lane's final checkpoint."""
    import numpy as np

    world = sizes["world_size"]
    round_diff = pallas_round_vs_xla(sizes)
    if max(round_diff.values()) > ROUND_TOLERANCE:
        raise RuntimeError(
            f"pallas round differs from the XLA lane by {round_diff} "
            f"(tolerance {ROUND_TOLERANCE})")
    line = sgp_w4(sizes, cache_dir, "sgp_w4_pallas",
                  sizes["gossip_kernel"])
    xla = checkpoint_params(os.path.join(OUT_DIR, "sgp_w4"), world)
    pallas = checkpoint_params(line["run_dir"], world)
    worst = max(float(np.max(np.abs(np.asarray(xla[k], np.float32)
                                    - np.asarray(pallas[k], np.float32))))
                for k in xla)
    scale = max(float(np.max(np.abs(np.asarray(v, np.float32))))
                for v in xla.values())
    return dict(line, round_max_abs_diff_vs_xla=round_diff,
                max_abs_diff_vs_xla=worst, param_scale=scale,
                bit_equal=worst == 0.0)


# -- the script -------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip path and what it is "
                         "compared with")
    chips = ap.parse_args(argv).chips
    # a collective that never completes must not outlive the time limit:
    # dump every thread's stack and exit non-zero
    faulthandler.dump_traceback_later(DEADLINE_S, exit=True,
                                      file=sys.__stderr__)
    try:
        return run(chips)
    finally:
        faulthandler.cancel_dump_traceback_later()


def run(chips: int) -> int:
    # first: where compiled programs are kept (before any backend exists)
    from stochastic_gradient_push_tpu.utils.compile_cache import (
        place_compile_cache)

    cache_dir = place_compile_cache()

    import jax

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    if device["platform"] != "tpu" or len(devices) < chips:
        say({"ok": False, "device": device,
             "reason": f"needs {chips} TPU chip(s); this script has no "
                       "other path"})
        return 1
    say({"phase": "device", "ok": True, **device,
         "compile_cache_dir": cache_dir,
         "compile_cache_entries_at_start": cache_entries(cache_dir)})

    if chips == 1:
        phases = [(resnet50_sgp, "resnet50_sgp"),
                  (lm_dense_flash, "lm_dense_flash"),
                  (grouped_kernels, "grouped_kernels")]
    else:
        phases = [(placement_w4, "w4"), (sgp_w4, "w4"), (ar_w4, "w4"),
                  (sgp_w4_pallas, "w4")]
    for phase, key in phases:
        try:
            say(phase(SIZES[key], cache_dir))
        except (Exception, SystemExit) as e:
            traceback.print_exc()
            say({"ok": False, "device": device, "phase": phase.__name__,
                 "error": f"{type(e).__name__}: {e}"[:4000]})
            return 1
    say({"ok": True, "device": device})
    return 0


if __name__ == "__main__":
    sys.exit(main())
