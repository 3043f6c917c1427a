"""The one batch generator: a traffic file's parameters in, resident
device batches out.

A traffic file (``benchmark/traffic/<name>.json``) names a ``kind`` and
its parameters.  Batches are drawn on the device from ``--seed`` inside
one jitted program per kind and stay there: the window picks among them
and never touches the host's memory.  Every seed gives the same shapes,
so a seed changes the values and never the work.

* ``images``: a class-dependent mean (a coarse ``grid`` x ``grid``
  pattern per class, upsampled) plus unit noise — learnable, so the loss
  falls from ln(classes).
* ``tokens``: a Zipf unigram whose ranks are rotated by a small hidden
  state that follows a sticky Markov chain — a skewed unigram with a
  first-order dependency, so the loss falls below ln(vocab).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def fold_seed(seed: int) -> int:
    """``--seed`` may need more than 32 signed bits; the program's parsers
    and ``PRNGKey`` get a non-negative int32 that still differs per seed."""
    return int(seed) % (2 ** 31 - 1)


def _image_batch(key, class_means, *, ranks, batch, size, channels,
                 classes, signal):
    kl, kn = jax.random.split(key)
    labels = jax.random.randint(kl, (ranks, batch), 0, classes, jnp.int32)
    grid = class_means.shape[1]
    mean = class_means[labels]                    # [R, B, g, g, C]
    mean = jnp.repeat(jnp.repeat(mean, size // grid, axis=2),
                      size // grid, axis=3)
    noise = jax.random.normal(kn, (ranks, batch, size, size, channels),
                              jnp.float32)
    return signal * mean + noise, labels


def _images(p: dict, key, n_batches: int, shardings):
    size, grid = int(p["image_size"]), int(p["class_grid"])
    if size % grid:
        raise ValueError(f"image_size {size} is no multiple of class_grid "
                         f"{grid}")
    k_means, k_batches = jax.random.split(key)
    class_means = jax.random.normal(
        k_means, (int(p["classes"]), grid, grid, int(p["channels"])),
        jnp.float32)
    draw = jax.jit(functools.partial(
        _image_batch, ranks=int(p["ranks"]), batch=int(p["batch_per_rank"]),
        size=size, channels=int(p["channels"]), classes=int(p["classes"]),
        signal=float(p["signal"])), out_shardings=shardings)
    return [draw(k, class_means)
            for k in jax.random.split(k_batches, n_batches)]


def _token_batch(key, cdf, offsets, *, ranks, batch, seq_len, vocab,
                 states, stay):
    k_state, k_jump, k_rank = jax.random.split(key, 3)
    shape = (ranks, batch, seq_len + 1)
    # hidden state: re-drawn where the chain jumps, else carried along —
    # the state at t is the draw at the last jump at or before t
    jump = jax.random.uniform(k_jump, shape) >= stay
    jump = jump.at[..., 0].set(True)
    drawn = jax.random.randint(k_state, shape, 0, states, jnp.int32)
    last_jump = jax.lax.cummax(
        jnp.where(jump, jnp.arange(seq_len + 1), 0), axis=2)
    state = jnp.take_along_axis(drawn, last_jump, axis=2)
    rank = jnp.searchsorted(cdf, jax.random.uniform(k_rank, shape))
    seq = (jnp.minimum(rank, vocab - 1).astype(jnp.int32)
           + offsets[state]) % vocab
    return seq[..., :-1], seq[..., 1:]


def _tokens(p: dict, key, n_batches: int, shardings):
    vocab, states = int(p["vocab"]), int(p["hidden_states"])
    weights = 1.0 / np.arange(1, vocab + 1) ** float(p["zipf_exponent"])
    cdf = jnp.asarray(np.cumsum(weights) / weights.sum(), jnp.float32)
    k_off, k_batches = jax.random.split(key)
    offsets = jax.random.randint(k_off, (states,), 0, vocab, jnp.int32)
    draw = jax.jit(functools.partial(
        _token_batch, ranks=int(p["ranks"]), batch=int(p["batch_per_rank"]),
        seq_len=int(p["seq_len"]), vocab=vocab, states=states,
        stay=float(p["stay"])), out_shardings=shardings)
    return [draw(k, cdf, offsets)
            for k in jax.random.split(k_batches, n_batches)]


KINDS = {"images": _images, "tokens": _tokens}


def make_batches(traffic: dict, seed: int, shardings=None) -> list[tuple]:
    """``resident_batches`` pairs ``(inputs, targets)``, leading dimension
    ``ranks``, laid out as ``shardings`` (one per array) says, where given."""
    kind = traffic["kind"]
    if kind not in KINDS:
        raise KeyError(f"traffic kind {kind!r} unknown; the generator "
                       f"knows {sorted(KINDS)}")
    key = jax.random.PRNGKey(fold_seed(seed))
    return KINDS[kind](traffic, key, int(traffic["resident_batches"]),
                       shardings)
