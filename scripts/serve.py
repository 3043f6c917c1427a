#!/usr/bin/env python
"""serve — gossip-trained checkpoints behind a paged-attention stack.

Ingests a run's reshardable checkpoint set (``checkpoint_r*_n*.ckpt``),
collapses it to the push-sum consensus (serve/load.py — the exact
``supervise.reshard`` algebra), and serves it with continuous batching
over a paged KV cache (serve/engine.py + serve/scheduler.py), driving
synthetic traffic and stamping the serving BENCH numbers into
``artifacts/bench_serve.json``.

Usage:
    # serve an LM checkpoint set with synthetic traffic:
    python scripts/serve.py RUN_DIR --n_heads 4 --requests 200

    # open-loop Poisson traffic, events + spans into a trace dir:
    python scripts/serve.py RUN_DIR --n_heads 4 --rate_hz 50 \\
        --trace_dir /runs/serve1

    # the CI gate: train world-4 -> consensus ingest (bit-checked
    # against the reshard collapse) -> paged-vs-dense decode parity on
    # an interpret-mode model mesh -> 50 requests, zero page leaks:
    python scripts/serve.py --selftest

Exit codes: 0 clean, 1 selftest/serve failure, 2 unusable checkpoint
directory or configuration.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import sys

# die quietly when piped into `head` instead of tracebacking
signal.signal(signal.SIGPIPE, signal.SIG_DFL)

# CPU harness script (CI + selftest); operators serving on real
# accelerators set JAX_PLATFORMS themselves
os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np  # noqa: E402

# every bench/report consumer expects this key set in the artifact
ARTIFACT_KEYS = frozenset({
    "requests", "tokens", "elapsed_s", "tokens_per_sec",
    "p50_latency_s", "p99_latency_s", "page_occupancy_peak",
    "admission_rejections", "kv_bytes_per_token", "decode_steps"})


def _print_metrics(metrics: dict) -> None:
    print(f"serve: {metrics['requests']} request(s), "
          f"{metrics['tokens']} token(s), "
          f"{metrics['tokens_per_sec']:.1f} tok/s, latency p50 "
          f"{metrics['p50_latency_s'] * 1e3:.2f} ms  p99 "
          f"{metrics['p99_latency_s'] * 1e3:.2f} ms", flush=True)
    print(f"serve: peak page occupancy "
          f"{metrics['page_occupancy_peak']:.0%}, "
          f"{metrics['admission_rejections']} admission rejection(s), "
          f"kv {metrics['kv_bytes_per_token']:,} B/token, "
          f"{metrics['decode_steps']} decode step(s)", flush=True)


def _build_engine(params, info, args):
    """LMEngine for a transformer set, the synthetic digest engine for
    anything else (a hostsim fleet's vector checkpoints must still
    serve — same fallback as serve/child.py)."""
    from stochastic_gradient_push_tpu.serve.bench import SyntheticEngine
    from stochastic_gradient_push_tpu.serve.engine import (
        LMEngine, ServeConfig)

    is_lm = isinstance(params, dict) and "embed" in params
    cfg = ServeConfig(
        n_heads=(args.n_heads or 1), page_size=args.page_size,
        num_pages=args.num_pages, max_seqs=args.max_seqs,
        max_pages_per_seq=args.max_pages_per_seq)
    if not is_lm:
        flat = np.concatenate([
            np.asarray(v, np.float64).ravel()
            for v in _leaves(params)]) if params else np.zeros(1)
        seed = int(np.abs(flat).sum() * 1000) % (2 ** 31)
        return SyntheticEngine(cfg, seed=seed), 256
    if not args.n_heads:
        raise SystemExit("error: --n_heads is required to serve an LM "
                         "checkpoint (it is not recorded in the params)")
    mesh = None
    if args.model_shards > 1:
        import jax
        from jax.sharding import Mesh

        from stochastic_gradient_push_tpu.serve.load import (
            shard_params_for_decode)
        devs = jax.devices()
        if len(devs) < args.model_shards:
            raise SystemExit(f"error: --model_shards "
                             f"{args.model_shards} > {len(devs)} devices")
        mesh = Mesh(np.array(devs[:args.model_shards]), ("model",))
        params = shard_params_for_decode(params, mesh)
    vocab = int(np.shape(
        params["embed"]["embedding"] if mesh is None
        else np.asarray(params["embed"]["embedding"]))[0])
    return LMEngine(params, cfg, mesh=mesh), vocab


def _leaves(tree):
    if isinstance(tree, dict):
        for v in tree.values():
            yield from _leaves(v)
    elif tree is not None:
        yield tree


def serve_dir(args) -> int:
    from stochastic_gradient_push_tpu.serve.bench import (
        poisson_arrivals, run_bench, synthetic_requests, write_artifact)
    from stochastic_gradient_push_tpu.serve.load import (
        ConsensusIngestError, load_consensus)
    from stochastic_gradient_push_tpu.supervise.reshard import (
        CheckpointMetaError, TornCheckpointError)
    from stochastic_gradient_push_tpu.telemetry import make_run_telemetry

    try:
        params, _, info = load_consensus(args.run_dir, args.tag,
                                         world=args.world)
    except (ConsensusIngestError, TornCheckpointError,
            CheckpointMetaError) as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    print(f"serve: ingested consensus of world {info.world} "
          f"({len(info.files)} file(s), step {info.step}, "
          f"{info.in_flight_folded} in-flight slot(s) folded"
          + (", EF residual forfeited" if info.ef_forfeited else "")
          + ")", flush=True)

    engine, vocab = _build_engine(params, info, args)
    requests = synthetic_requests(
        args.requests, seed=args.seed, vocab=min(vocab, 256),
        prompt_tokens=(args.min_prompt, args.max_prompt),
        new_tokens=(args.min_new, args.max_new))
    arrivals = (poisson_arrivals(args.requests, args.rate_hz, args.seed)
                if args.rate_hz > 0 else None)
    rt = make_run_telemetry(args.trace_dir, rank=0)
    if rt.registry is not None:
        rt.registry.emit("run_meta", {
            "algorithm": "serve", "world": info.world, "serve": True,
            "model_source": info.to_dict()})
    metrics, _ = run_bench(engine, requests, arrivals=arrivals,
                           tracer=rt.tracer, registry=rt.registry)
    rt.finish()
    _print_metrics(metrics)
    path = write_artifact(args.artifact, metrics, tracer=rt.tracer,
                          extra={"ingest": info.to_dict()})
    print(f"serve: artifact -> {path}", flush=True)
    return 0


# -- selftest ---------------------------------------------------------------


def selftest() -> int:
    """The CI gate: the whole train -> checkpoint -> ingest -> serve
    path on a world-4 CPU mesh, with the ingest held bit-equal to the
    reshard collapse and paged decode held to the dense model."""
    import tempfile

    import flax.serialization
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from stochastic_gradient_push_tpu.run import gossip_lm
    from stochastic_gradient_push_tpu.serve.bench import (
        run_bench, synthetic_requests, write_artifact)
    from stochastic_gradient_push_tpu.serve.engine import (
        LMEngine, ServeConfig)
    from stochastic_gradient_push_tpu.serve.load import (
        load_consensus, shard_params_for_decode)
    from stochastic_gradient_push_tpu.serve.paged_attention import (
        paged_attention_reference, sharded_paged_decode)
    from stochastic_gradient_push_tpu.supervise.reshard import (
        reshard_state)
    from stochastic_gradient_push_tpu.telemetry import make_run_telemetry
    from stochastic_gradient_push_tpu.utils import make_logger
    from stochastic_gradient_push_tpu.utils.checkpoint import (
        CheckpointManager)

    ok = True

    def expect(cond, what):
        nonlocal ok
        if not cond:
            ok = False
            print(f"FAIL: {what}", flush=True)

    # 1. train a tiny LM with push-sum gossip on the world-4 mesh,
    #    per-rank different data (the consensus is a real mixture)
    WORLD, BATCH, SEQ, VOCAB, HEADS = 4, 2, 16, 64, 4
    EPOCHS, ITR = 2, 4
    job = gossip_lm.build_training(gossip_lm.parse_args([
        "--world_size", str(WORLD), "--graph_type", "0",
        "--vocab_size", str(VOCAB), "--d_model", "32", "--n_layers", "2",
        "--n_heads", str(HEADS), "--d_ff", "64", "--seq_len", str(SEQ),
        "--attn", "full", "--batch_size", str(BATCH), "--lr", "0.4",
        "--seed", "0"]), make_logger("serve-selftest", False))
    model, train_fn, state = job.model, job.train_fn, job.state
    rng = np.random.default_rng(0)
    loss = float("nan")
    for _ in range(EPOCHS * ITR):
        toks = rng.integers(1, VOCAB, size=(WORLD, BATCH, SEQ + 1))
        toks = toks.astype(np.int32)
        state, metrics = train_fn(state, jnp.asarray(toks[..., :-1]),
                                  jnp.asarray(toks[..., 1:]))
        loss = float(np.asarray(metrics["loss"])[0])
    expect(np.isfinite(loss), f"train loss not finite: {loss}")
    print(f"serve selftest: trained world {WORLD} for {EPOCHS} epochs "
          f"(loss {loss:.3f})", flush=True)

    with tempfile.TemporaryDirectory() as d:
        # 2. save reshardable (one process holding all 4 rank rows) and
        #    ingest: params must be BIT-equal to the reshard collapse
        CheckpointManager(d, rank=0, world_size=WORLD).save(
            state, {"step": int(np.asarray(state.step)[0]),
                    "world": WORLD, "rows": WORLD, "process_id": 0,
                    "num_processes": 1, "epoch": EPOCHS, "itr": 0})
        with open(os.path.join(
                d, f"checkpoint_r0_n{WORLD}.ckpt"), "rb") as f:
            raw = flax.serialization.msgpack_restore(f.read())
        want = reshard_state(raw["state"], WORLD, 1)["params"]
        params, _, info = load_consensus(d)
        expect(info.world == WORLD, f"ingest world {info.world}")

        def compare(a, b, path=""):
            nonlocal ok
            if isinstance(a, dict):
                for k in a:
                    compare(a[k], b[k], f"{path}/{k}")
                return
            if not np.array_equal(np.asarray(a),
                                  np.asarray(b)[0]):
                ok = False
                print(f"FAIL: ingest{path} != reshard collapse",
                      flush=True)

        compare(params, want)
        print("serve selftest: consensus ingest bit-equal to "
              "reshard_state collapse", flush=True)

        # 3. decode-mesh placement + paged-vs-dense parity, both the
        #    raw kernel (f32 tolerance) and the whole greedy engine
        dmesh = Mesh(np.array(jax.devices()[:2]), ("model",))
        r = np.random.default_rng(1)
        q = r.standard_normal((4, HEADS, 8)).astype(np.float32)
        kp = r.standard_normal((HEADS, 7, 4, 8)).astype(np.float32)
        vp = r.standard_normal((HEADS, 7, 4, 8)).astype(np.float32)
        pi = r.integers(0, 7, size=(4, 6)).astype(np.int32)
        lengths = np.array([1, 9, 16, 24], np.int32)
        out = sharded_paged_decode(dmesh, q, kp, vp, pi, lengths,
                                   use_pallas=True, interpret=True)
        err = float(np.max(np.abs(
            np.asarray(out)
            - paged_attention_reference(q, kp, vp, pi, lengths))))
        expect(err < 1e-5, f"paged kernel vs dense reference: {err}")
        print(f"serve selftest: paged decode kernel on interpret mesh, "
              f"max err {err:.2e}", flush=True)

        sharded = shard_params_for_decode(params, dmesh)
        engine = LMEngine(
            sharded,
            ServeConfig(n_heads=HEADS, page_size=4, num_pages=32,
                        max_seqs=4, max_pages_per_seq=4,
                        use_pallas=True, interpret=True),
            mesh=dmesh)
        prompt, n_new = [5, 17, 3, 29], 5
        slot, tok = engine.start(list(prompt), len(prompt) + n_new)
        got = [tok]
        while len(got) < n_new:
            got.append(engine.step([slot])[slot])
        engine.finish(slot)
        engine.pages.assert_quiescent()
        pjax = jax.tree.map(jnp.asarray, params)
        seq, dense = list(prompt), []
        for _ in range(n_new):
            logits = model.apply({"params": pjax},
                                 jnp.asarray([seq], jnp.int32))
            nxt = int(jnp.argmax(logits[0, -1]))
            dense.append(nxt)
            seq.append(nxt)
        expect(got == dense,
               f"paged greedy decode {got} != dense model {dense}")
        print(f"serve selftest: engine greedy continuation matches the "
              f"dense model: {got}", flush=True)

        # 4. continuous batching: 50 requests through the real engine,
        #    all complete, zero page leaks (run_bench asserts
        #    quiescence), artifact written + schema-checked
        N_REQ = 50
        rt = make_run_telemetry(os.path.join(d, "trace"), rank=0)
        rt.registry.emit("run_meta", {
            "algorithm": "serve", "world": WORLD, "serve": True,
            "model_source": info.to_dict()})
        requests = synthetic_requests(N_REQ, seed=9, vocab=VOCAB,
                                      prompt_tokens=(2, 6),
                                      new_tokens=(2, 5))
        metrics, completions = run_bench(
            engine, requests, tracer=rt.tracer, registry=rt.registry)
        rt.finish()
        expect(metrics["requests"] == N_REQ,
               f"{metrics['requests']}/{N_REQ} requests completed")
        expect(metrics["admission_rejections"] == 0,
               f"{metrics['admission_rejections']} unexpected "
               "rejections")
        expect(all(len(c.tokens) == requests[c.rid].max_new_tokens
                   for c in completions), "token budgets not honored")
        expect(metrics["kv_bytes_per_token"]
               == engine.kv_bytes_per_token() > 0,
               f"kv bytes/token {metrics['kv_bytes_per_token']}")

        path = write_artifact(
            os.path.join("artifacts", "bench_serve.json"), metrics,
            tracer=rt.tracer, extra={"ingest": info.to_dict()})
        with open(path) as f:
            doc = json.load(f)
        expect(set(doc) == {"bench", "trace"},
               f"artifact layout: {sorted(doc)}")
        missing = ARTIFACT_KEYS - set(doc.get("bench", {}))
        expect(not missing, f"artifact missing keys: {sorted(missing)}")
        b = doc.get("bench", {})
        expect(b.get("tokens_per_sec", 0) > 0, "tokens/sec not stamped")
        expect(b.get("p99_latency_s", 0) >= b.get("p50_latency_s", 1),
               "p99 < p50")
        _print_metrics(metrics)
        print(f"serve selftest: artifact -> {path}", flush=True)

    print("serve selftest:", "OK" if ok else "FAILED", flush=True)
    return 0 if ok else 1


# -- entry ------------------------------------------------------------------


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("run_dir", nargs="?",
                   help="checkpoint directory (checkpoint_r*_n*.ckpt)")
    p.add_argument("--tag", default="")
    p.add_argument("--world", type=int, default=None,
                   help="checkpoint world to ingest (default: newest)")
    p.add_argument("--n_heads", type=int, default=None,
                   help="attention heads of the saved LM (required for "
                        "LM sets)")
    p.add_argument("--model_shards", type=int, default=1,
                   help="KV-head shards over a 1-D model mesh")
    p.add_argument("--page_size", type=int, default=8)
    p.add_argument("--num_pages", type=int, default=64)
    p.add_argument("--max_seqs", type=int, default=4)
    p.add_argument("--max_pages_per_seq", type=int, default=8)
    p.add_argument("--requests", type=int, default=100)
    p.add_argument("--rate_hz", type=float, default=0.0,
                   help="Poisson arrival rate (0 = closed loop)")
    p.add_argument("--min_prompt", type=int, default=4)
    p.add_argument("--max_prompt", type=int, default=12)
    p.add_argument("--min_new", type=int, default=2)
    p.add_argument("--max_new", type=int, default=8)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--trace_dir", default=None,
                   help="events.jsonl + trace.json output directory")
    p.add_argument("--artifact",
                   default=os.path.join("artifacts", "bench_serve.json"))
    p.add_argument("--selftest", action="store_true",
                   help="train -> ingest -> serve CI gate")
    args = p.parse_args(argv)
    if args.selftest:
        return selftest()
    if not args.run_dir:
        p.error("run_dir required (or --selftest)")
    return serve_dir(args)


if __name__ == "__main__":
    sys.exit(main())
