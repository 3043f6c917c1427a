"""Convergence-parity study: AR vs SGP vs OSGP(×staleness) vs D-PSGD vs
AD-PSGD through the full Trainer stack on the 8-rank virtual CPU mesh.

Quantifies the staleness trade (SURVEY.md §7 hard parts #3-4): overlap
mode delays gossip consumption by synch_freq+1 steps, and AD-PSGD replaces
host-async bilateral averaging with synchronous perfect matchings.  Each
config trains the same TinyCNN on the same synthetic data; the artifact is
a per-epoch validation-accuracy figure + final-accuracy table
(docs/convergence_parity.png, docs/CONVERGENCE_PARITY.md).

Run:  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      PYTHONPATH=. python examples/convergence_parity.py
"""

import os

import jax
import numpy as np

from stochastic_gradient_push_tpu.data import (
    DistributedSampler,
    ShardedLoader,
    synthetic_classification,
)
from stochastic_gradient_push_tpu.models import TinyCNN
from stochastic_gradient_push_tpu.parallel import make_gossip_mesh
from stochastic_gradient_push_tpu.topology import (
    DynamicBipartiteExponentialGraph,
    NPeerDynamicDirectedExponentialGraph,
)
from stochastic_gradient_push_tpu.train.loop import Trainer, TrainerConfig

WORLD, BATCH, CLASSES, IMG = 8, 8, 40, 12
EPOCHS = 24
THRESH = 90.0

# fixed-order categorical palette (validated; see dataviz palette.md)
PALETTE = ["#2a78d6", "#eb6834", "#1baf7a", "#eda100", "#e87ba4",
           "#008300"]

CONFIGS = [
    ("AR", dict(all_reduce=True, graph_class=None)),
    ("SGP", dict(push_sum=True)),
    ("OSGP", dict(push_sum=True, overlap=True)),
    ("OSGP sf=2", dict(push_sum=True, overlap=True, synch_freq=2)),
    ("D-PSGD", dict(push_sum=False,
                    graph_class=DynamicBipartiteExponentialGraph)),
    ("AD-PSGD", dict(bilat=True,
                     graph_class=DynamicBipartiteExponentialGraph)),
]


def run_config(name, overrides, data, out_dir):
    images, labels, val_images, val_labels = data
    kwargs = dict(
        graph_class=NPeerDynamicDirectedExponentialGraph,
        lr=0.15, warmup=False, lr_schedule={20: 0.1},
        num_iterations_per_training_epoch=8,
        batch_size=BATCH, num_epochs=EPOCHS, num_itr_ignore=0,
        checkpoint_dir=os.path.join(out_dir, name.replace(" ", "_")),
        num_classes=CLASSES, verbose=False, heartbeat_timeout=0)
    kwargs.update(overrides)
    cfg = TrainerConfig(**kwargs)
    mesh = make_gossip_mesh(WORLD)
    trainer = Trainer(cfg, TinyCNN(num_classes=CLASSES), mesh,
                      sample_input_shape=(BATCH, IMG, IMG, 3))
    state = trainer.init_state()
    sampler = DistributedSampler(len(images), WORLD)
    loader = ShardedLoader(images, labels, BATCH, sampler)
    val_sampler = DistributedSampler(len(val_images), WORLD)
    val_loader = ShardedLoader(val_images, val_labels, BATCH, val_sampler)

    curve = []
    orig_validate = trainer.validate

    def tracking_validate(state, alg, vl):
        v = orig_validate(state, alg, vl)
        curve.append(v)
        return v

    trainer.validate = tracking_validate
    state, result = trainer.fit(state, loader, sampler, val_loader)
    print(f"{name}: final {curve[-1]:.2f}% best {result['best_prec1']:.2f}%",
          flush=True)
    return curve, result


def main():
    out_dir = "/tmp/convergence_parity"
    os.makedirs(out_dir, exist_ok=True)
    n = WORLD * BATCH * 24
    n_val = WORLD * BATCH * 4
    all_images, all_labels = synthetic_classification(
        n + n_val, num_classes=CLASSES, image_size=IMG, seed=7,
        noise=1.5)
    data = (all_images[:n], all_labels[:n],
            all_images[n:], all_labels[n:])

    curves = {}
    finals = {}
    for name, overrides in CONFIGS:
        curve, result = run_config(name, overrides, data, out_dir)
        curves[name] = curve
        to_thresh = next((i + 1 for i, v in enumerate(curve)
                          if v >= THRESH), None)
        finals[name] = (curve[-1], result["best_prec1"], to_thresh)

    # figure: one line per algorithm, fixed-order palette, direct labels
    import matplotlib
    matplotlib.use("Agg")
    import matplotlib.pyplot as plt

    fig, ax = plt.subplots(figsize=(8, 4.8), dpi=150)
    for (name, curve), color in zip(curves.items(), PALETTE):
        xs = np.arange(1, len(curve) + 1)
        ax.plot(xs, curve, color=color, linewidth=2, label=name)
        ax.annotate(name, (xs[-1], curve[-1]), xytext=(4, 0),
                    textcoords="offset points", fontsize=8, color="#333")
    ax.set_xlabel("validation point (every 8 steps)")
    ax.set_ylabel("validation top-1 (%)")
    ax.set_title("Convergence parity: decentralized algorithms, "
                 "8-rank mesh, TinyCNN/synthetic")
    ax.grid(True, color="#eeeeee", linewidth=0.8)
    ax.spines[["top", "right"]].set_visible(False)
    ax.legend(frameon=False, fontsize=8, loc="lower right")
    fig.tight_layout()
    fig.savefig("docs/convergence_parity.png")

    # preserve everything from the first non-toy section onward — those
    # sections are written by other studies (convergence_resnet.py,
    # convergence_lm.py transcriptions) and must survive regeneration
    preserved = ""
    try:
        with open("docs/CONVERGENCE_PARITY.md") as f:
            old = f.read()
        idx = old.find("## Non-toy parity")
        if idx >= 0:
            preserved = old[idx:]
    except OSError:
        pass
    with open("docs/CONVERGENCE_PARITY.md", "w") as f:
        f.write(
            "# Convergence parity across algorithms\n\n"
            "Same model (TinyCNN), data (synthetic, 10 classes), LR and "
            f"epochs ({EPOCHS}) for every algorithm on the 8-rank virtual "
            "CPU mesh, through the full Trainer/CLI stack "
            "(examples/convergence_parity.py; re-run to regenerate).\n\n"
            "| Algorithm | Final val top-1 | Best val top-1 | "
            f"Epochs to {THRESH:.0f}% |\n"
            "|-----------|-----------------|----------------|"
            "----------------|\n")
        for name, (final, best, to_t) in finals.items():
            f.write(f"| {name} | {final:.2f}% | {best:.2f}% | "
                    f"{to_t if to_t is not None else '—'} |\n")
        f.write(
            "\n![curves](convergence_parity.png)\n\n"
            "## Reading the staleness trade\n\n"
            "- **OSGP vs SGP**: at staleness 1 the overlap split is "
            "exact — the incoming share is applied before the next "
            "forward, so the training trajectory and (drained) "
            "validation MATCH sync SGP identically "
            "(test_osgp_val_params_drains_to_sync); the rows above "
            "coincide. The collective still overlaps backprop "
            "(distributed.py:571-588 semantics, compiled).\n"
            "- **OSGP sf=2** (synch_freq=2 → staleness 3): bounded "
            "staleness degrades mixing further; the gap vs SGP is the "
            "quantitative cost of the reference's non-blocking polling "
            "window (distributed.py:127-129).\n"
            "- **AD-PSGD** here is the synchronous perfect-matching "
            "formulation (ARCHITECTURE.md design decision): bilateral "
            "pair averages each step, no host asynchrony. Its curve "
            "bounds the *algorithmic* behavior; the reference's "
            "wall-clock staleness distribution is hardware-dependent "
            "and not reproducible in SPMD.\n")
        if preserved:
            f.write("\n" + preserved)
    print("wrote docs/convergence_parity.png, docs/CONVERGENCE_PARITY.md")


if __name__ == "__main__":
    main()
