"""LM convergence parity: AR vs SGP vs OSGP vs D-PSGD vs AD-PSGD on a
REAL byte corpus through the full gossip_lm CLI stack.

The second task family for the D3 acceptance claim (the ResNet study in
examples/convergence_parity.py / docs/CONVERGENCE_PARITY.md was the
first): every algorithm trains the same byte-level transformer on the
same real text (CPython stdlib sources — ~4 MB, deterministic), same LR
schedule, same fixed token budget, 8-rank virtual CPU mesh, with 10 %
of the corpus tail held out for validation.  Artifacts:

* ``docs/convergence_lm.png`` — val loss vs tokens AND vs wall-clock
  (the error-vs-time view the paper family uses,
  reference visualization/plotting.py:26-52)
* a final table (printed as JSON) with AR-relative final val loss/ppl
  -> transcribed into docs/CONVERGENCE_PARITY.md's LM section.

Run:  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      PYTHONPATH=. python examples/convergence_lm.py
"""

import glob
import json
import os
import time

import jax
import numpy as np

WORLD = 8
STEPS = int(os.environ.get("LM_STUDY_STEPS", "2500"))
VAL_EVERY = 100
OUT_DIR = os.environ.get("LM_STUDY_DIR", "/tmp/convergence_lm")
# model scale knobs (defaults = the headline study; LM_STUDY_SCALE=big
# runs the 4x-larger dose point recorded in CONVERGENCE_PARITY.md)
if os.environ.get("LM_STUDY_SCALE") == "big":
    D_MODEL, N_LAYERS, N_HEADS, D_FF, SEQ = 128, 4, 4, 512, 256
    FIG = "docs/convergence_lm_big.png"
else:
    D_MODEL, N_LAYERS, N_HEADS, D_FF, SEQ = 64, 2, 4, 256, 128
    FIG = "docs/convergence_lm.png"

# fixed-order categorical palette (validated; see dataviz palette.md)
PALETTE = ["#2a78d6", "#eb6834", "#1baf7a", "#eda100", "#e87ba4"]

# algorithm -> extra gossip_lm flags.  Everything else (model, data, LR,
# token budget) is IDENTICAL across configs; D-PSGD/AD-PSGD need the
# bipartite graph (doubly-stochastic / perfect matchings).
CONFIGS = [
    ("AR", ["--all_reduce", "True"]),
    ("SGP", []),
    ("OSGP", ["--overlap", "True"]),
    ("D-PSGD", ["--push_sum", "False", "--graph_type", "1"]),
    ("AD-PSGD", ["--bilat", "True", "--graph_type", "1"]),
]

BASE = ["--world_size", str(WORLD), "--seq_len", str(SEQ),
        "--d_model", str(D_MODEL), "--n_heads", str(N_HEADS),
        "--n_layers", str(N_LAYERS),
        "--d_ff", str(D_FF), "--batch_size", "2",
        "--num_steps", str(STEPS), "--warmup", "True",
        "--val_frac", "0.1", "--val_every", str(VAL_EVERY),
        "--val_batches", "8", "--print_freq", str(VAL_EVERY),
        "--seed", "47"]


def build_corpus(path: str) -> str:
    """~4 MB of real text: CPython stdlib sources, sorted, capped."""
    if os.path.exists(path):
        return path
    buf = bytearray()
    import sysconfig
    root = sysconfig.get_paths()["stdlib"]
    for f in sorted(glob.glob(os.path.join(root, "*.py"))):
        with open(f, "rb") as fh:
            buf += fh.read()
        if len(buf) >= 4_000_000:
            break
    with open(path, "wb") as fh:
        fh.write(bytes(buf[:4_000_000]))
    return path


def run_config(name, extra, corpus):
    from stochastic_gradient_push_tpu.run import gossip_lm

    ckpt = os.path.join(OUT_DIR, name.replace(" ", "_"))
    os.makedirs(ckpt, exist_ok=True)
    csv = os.path.join(ckpt, f"lm_out_n{WORLD}.csv")
    if os.environ.get("LM_STUDY_REUSE") == "1" and os.path.exists(csv):
        # reuse a finished arm's CSV (e.g. re-running one arm after a
        # val-semantics change).  Wall-clock is reconstructed from the
        # CSV's OWN final step and the run's seq (not the current
        # STEPS/SEQ globals — a stale CSV from another scale must not be
        # silently rescaled), using its train-throughput column; note
        # the CSV's tokens_per_sec excludes compile/validation wall, so
        # reused arms' wall axis is train-time-only (slightly tighter
        # than fresh arms' perf_counter wall).
        rows = np.atleast_1d(np.genfromtxt(csv, delimiter=",",
                                           names=True))
        csv_steps = float(rows["step"][-1])
        if int(csv_steps) != STEPS:
            raise SystemExit(
                f"{name}: existing CSV has {int(csv_steps)} steps but "
                f"LM_STUDY_STEPS={STEPS}; refusing to mix budgets — "
                "delete the arm's directory to re-run it")
        tps = float(np.mean(rows["tokens_per_sec"]))
        wall = csv_steps * WORLD * 2 * SEQ / max(tps, 1.0)
        print(f"{name}: reusing {csv} (wall reconstructed "
              f"{wall/60:.1f} min, train-time-only)", flush=True)
        return rows, wall
    t0 = time.perf_counter()
    gossip_lm.main(BASE + extra + [
        "--corpus_file", corpus, "--checkpoint_dir", ckpt])
    wall = time.perf_counter() - t0
    # atleast_1d: a single-row CSV genfromtxts to a 0-d structured array
    rows = np.atleast_1d(np.genfromtxt(csv, delimiter=",", names=True))
    return rows, wall


def main():
    os.makedirs(OUT_DIR, exist_ok=True)
    os.makedirs("docs", exist_ok=True)
    corpus = build_corpus(os.path.join(OUT_DIR, "corpus.bin"))

    curves, walls, finals = {}, {}, {}
    for name, extra in CONFIGS:
        rows, wall = run_config(name, extra, corpus)
        curves[name] = rows
        walls[name] = wall
        val = rows["val_loss"][np.isfinite(rows["val_loss"])]
        finals[name] = float(val[-1]) if len(val) else float("nan")
        print(f"{name}: final val_loss {finals[name]:.4f}  "
              f"wall {wall/60:.1f} min", flush=True)

    ar = finals["AR"]
    table = {
        name: {
            "final_val_loss": round(v, 4),
            "final_val_ppl": round(float(np.exp(v)), 3),
            "delta_vs_AR": round(v - ar, 4),
            "ppl_ratio_vs_AR": round(float(np.exp(v - ar)), 4),
            "wall_min": round(walls[name] / 60, 1),
        } for name, v in finals.items()}
    print(json.dumps({"lm_parity": table}), flush=True)

    import matplotlib
    matplotlib.use("Agg")
    import matplotlib.pyplot as plt

    fig, (ax1, ax2) = plt.subplots(1, 2, figsize=(11, 4.4), dpi=150)
    tokens_per_step = WORLD * 2 * SEQ
    for (name, rows), color in zip(curves.items(), PALETTE):
        m = np.isfinite(rows["val_loss"])
        steps = rows["step"][m]
        val = rows["val_loss"][m]
        ax1.plot(steps * tokens_per_step / 1e6, val, color=color,
                 linewidth=1.8, label=name)
        # wall-clock axis: steps are even paced within a run, so scale
        # the step axis by the run's measured wall time
        ax2.plot(steps / rows["step"][-1] * walls[name] / 60, val,
                 color=color, linewidth=1.8, label=name)
    for ax, xl in ((ax1, "tokens (millions)"), (ax2, "wall-clock (min)")):
        ax.set_xlabel(xl)
        ax.set_ylabel("validation loss (nats/byte)")
        ax.grid(True, color="#eeeeee", linewidth=0.8)
        ax.spines[["top", "right"]].set_visible(False)
    ax1.legend(frameon=False, fontsize=8, loc="upper right")
    ax1.set_title("LM convergence parity: same token budget")
    ax2.set_title("error vs wall-clock")
    fig.suptitle(f"Byte-level LM (d{D_MODEL} L{N_LAYERS}), real corpus "
                 "(CPython stdlib), 8-rank mesh", fontsize=10)
    fig.tight_layout()
    fig.savefig(FIG)
    print(f"wrote {FIG}", flush=True)


if __name__ == "__main__":
    main()
