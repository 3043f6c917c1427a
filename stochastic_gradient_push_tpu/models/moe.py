"""Mixture-of-experts FFN with expert parallelism over a mesh axis.

Completes the parallelism alphabet (dp × sp × tp × **ep**): experts shard
over a manual ``ep`` mesh axis, tokens route top-1 (switch style) with a
capacity limit, and two ``lax.all_to_all`` collectives move token slots to
their experts' shards and back.  Each shard computes only its local experts
over only the tokens routed to them — the compute- and memory-efficient
formulation, not a masked dense mixture.

Functional layer (explicit weights) so it slots into the same
shard_map-based step structure as everything else:

    y, aux = switch_moe_ffn(x, router_w, w1, w2, ep_axis="ep")

``w1``/``w2`` carry the *local* expert slices (global ``[E, ...]`` arrays
sharded over ``ep`` via ``in_specs=P("ep")``).  With ``ep_axis=None`` the
same code runs single-shard with all experts — the numerical reference the
tests pin the sharded version against.

Beside it, the layer of the published sparse-expert LMs
(:func:`topk_moe_ffn`): sigmoid scores, ``per_token`` experts a token
chosen with a selection bias, **no capacity and no token dropped**,
SiLU-gated experts in the compute dtype.  The (token, choice) pairs are
sorted by expert and each expert's rows go through grouped matrix
products (``ops/grouped_matmul.py``: Pallas kernels on a TPU,
``lax.ragged_dot`` elsewhere), at shapes that do not depend on the
split and at the cost of the rows held.  The layer is told which experts
it holds: it routes over all of them and computes its own experts' part
of the result — what expert parallelism asks of a shard, here without
the exchange.  DeepSeek-V3's form of it (arXiv:2412.19437 §2.1.2) adds
a scale on the normalised weights and a shared expert every token
passes through.
"""

from __future__ import annotations

import typing as tp

import jax
import jax.numpy as jnp
from jax import lax

from ..ops.grouped_matmul import grouped_dot
from ..telemetry import names

__all__ = ["switch_moe_ffn", "moe_capacity", "ExpertsConfig",
           "topk_moe_ffn"]


def moe_capacity(num_tokens: int, num_experts: int,
                 capacity_factor: float = 1.25) -> int:
    """Per-expert token slots per source shard."""
    return max(1, int(num_tokens * capacity_factor / num_experts))


def switch_moe_ffn(x, router_w, w1, w2, ep_axis: str | None = None,
                   capacity_factor: float = 1.25):
    """Top-1 switch MoE feed-forward.

    Args:
      x: ``[T, D]`` tokens (this shard's tokens when ``ep_axis`` is set).
      router_w: ``[D, E]`` router weights (replicated; E = total experts).
      w1: ``[E_local, D, F]`` up-projections (local expert slice).
      w2: ``[E_local, F, D]`` down-projections.
      ep_axis: mesh axis experts are sharded over (None = single shard).
      capacity_factor: slots per expert = T·cf/E per source shard; tokens
        over capacity receive zero expert output — callers supply the
        residual connection that makes them pass through (standard switch
        usage).

    Returns ``(y [T, D], aux)`` where aux carries the load-balancing loss
    (Switch Transformer's fraction·probability dot product) and the
    fraction of dropped tokens.
    """
    t, d = x.shape
    e_local = w1.shape[0]
    ep = lax.axis_size(ep_axis) if ep_axis is not None else 1
    e_total = e_local * ep
    if router_w.shape[-1] != e_total:
        raise ValueError(
            f"router is over {router_w.shape[-1]} experts but weights "
            f"provide {e_total} ({e_local} × {ep} shards)")
    cap = moe_capacity(t, e_total, capacity_factor)

    logits = x @ router_w                                    # [T, E]
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    expert_idx = jnp.argmax(probs, axis=-1)                  # [T]
    top_prob = jnp.take_along_axis(
        probs, expert_idx[:, None], axis=-1)[:, 0]           # [T]

    onehot = jax.nn.one_hot(expert_idx, e_total,
                            dtype=jnp.float32)               # [T, E]
    # position of each token within its chosen expert's queue
    cum = jnp.cumsum(onehot.astype(jnp.int32), axis=0)       # [T, E]
    pos = jnp.take_along_axis(
        cum, expert_idx[:, None], axis=-1)[:, 0] - 1         # [T] int32
    kept = pos < cap
    # out-of-capacity tokens index slot == cap → one_hot gives all-zeros
    slot = jax.nn.one_hot(jnp.where(kept, pos, cap), cap,
                          dtype=jnp.float32)                 # [T, C]
    dispatch = onehot[:, :, None] * slot[:, None, :]         # [T, E, C]

    x_slots = jnp.einsum("tec,td->ecd", dispatch,
                         x.astype(jnp.float32))              # [E, C, D]

    if ep_axis is not None:
        # [E, C, D] → this shard's experts with every shard's slots:
        # [E_local, ep·C, D]
        x_slots = lax.all_to_all(x_slots, ep_axis, split_axis=0,
                                 concat_axis=1, tiled=True)

    h = jnp.einsum("ecd,edf->ecf", x_slots, w1.astype(jnp.float32))
    h = jax.nn.gelu(h)
    y_slots = jnp.einsum("ecf,efd->ecd", h, w2.astype(jnp.float32))

    if ep_axis is not None:
        y_slots = lax.all_to_all(y_slots, ep_axis, split_axis=1,
                                 concat_axis=0, tiled=True)  # [E, C, D]

    combine = dispatch * top_prob[:, None, None]             # [T, E, C]
    y = jnp.einsum("tec,ecd->td", combine, y_slots)

    # Switch load-balancing loss: E · Σ_e (token fraction)·(mean prob)
    frac = jnp.mean(onehot, axis=0)
    mean_prob = jnp.mean(probs, axis=0)
    aux = {
        "load_balance_loss": e_total * jnp.sum(frac * mean_prob),
        "dropped_fraction": 1.0 - jnp.mean(kept.astype(jnp.float32)),
    }
    return y.astype(x.dtype), aux


class ExpertsConfig(tp.NamedTuple):
    """Sizes of the top-k expert layer, as the source's ``config.json``
    gives them.  ``held`` is the half-open range of experts whose weights
    live here (``None``: all of them); the router is ``n_experts`` wide
    whatever is held.  ``scale`` multiplies the normalised weights
    (``routed_scaling_factor``); ``d_shared`` is the width of a shared
    expert every token passes through (0: none)."""

    n_experts: int = 32
    per_token: int = 4
    d_ff: int = 1792
    held: tuple[int, int] | None = None
    scale: float = 1.0
    d_shared: int = 0

    @property
    def first(self) -> int:
        return self.held[0] if self.held else 0

    @property
    def n_held(self) -> int:
        return self.held[1] - self.held[0] if self.held else self.n_experts

    def check(self) -> None:
        first, end = self.held or (0, self.n_experts)
        if not 0 <= first < end <= self.n_experts:
            raise ValueError(f"experts held [{first}, {end}) are no part "
                             f"of the router's {self.n_experts}")
        if not 1 <= self.per_token <= self.n_experts:
            raise ValueError(f"{self.per_token} experts a token of "
                             f"{self.n_experts}")


# The two moves of rows between token order and expert order.  Each is a
# gather, and so is its transpose: ``order`` lists the pairs by expert,
# ``place`` is its inverse, and autodiff's scatter-add never appears.
# Pairs are numbered choice-major (pair ``j * T + t`` is token ``t``'s
# ``j``-th choice): ``[k * T, D]`` then splits into ``[k, T, D]`` on its
# leading dimension, which a tiled layout gives for nothing, where
# ``[T, k, D]`` would be another array.

def _take(rows, index):
    return jnp.take(rows, index, axis=0, mode="clip")


@jax.custom_vjp
def _rows_to_experts(x, order, place, held):
    """``x`` ``[T, D]`` to ``[k * T, D]``: row ``i`` is the token of pair
    ``order[i]``.  Rows of pairs not held come last and are read by no
    product."""
    return _take(x, order % x.shape[0])


def _to_experts_fwd(x, order, place, held):
    return _rows_to_experts(x, order, place, held), (place, held, x.shape[0])


def _to_experts_bwd(res, d_rows):
    place, held, t = res
    back = jnp.where(held[:, None], _take(d_rows, place), 0)
    d_x = back.reshape(-1, t, back.shape[-1]).astype(jnp.float32).sum(0)
    return d_x.astype(d_rows.dtype), None, None, None


_rows_to_experts.defvjp(_to_experts_fwd, _to_experts_bwd)


@jax.custom_vjp
def _rows_from_experts(out, order, place, held):
    """``out`` ``[k * T, D]`` in expert order back to pair order, zero for
    the pairs not held."""
    return jnp.where(held[:, None], _take(out, place), 0)


def _from_experts_fwd(out, order, place, held):
    return _rows_from_experts(out, order, place, held), (order, held)


def _from_experts_bwd(res, d_back):
    order, held = res
    # in expert order the held pairs come first
    live = (jnp.arange(held.shape[0]) < held.sum())[:, None]
    return jnp.where(live, _take(d_back, order), 0), None, None, None


_rows_from_experts.defvjp(_from_experts_fwd, _from_experts_bwd)


def topk_moe_ffn(x, router_w, bias, w_gate_up, w_down, *, per_token: int,
                 first: int = 0, dtype=None, scale: float = 1.0,
                 shared=None):
    """Top-k expert feed-forward with no token dropped, over the experts
    held here.

    Args:
      x: ``[T, D]`` tokens, float32 (the norm's output).
      router_w: ``[D, E]`` float32, ``E`` the published router width.
      bias: ``[E]`` selection bias (it chooses, it does not weigh; no
        gradient).
      w_gate_up: ``[n, D, 2 F]`` the held experts' gate and up
        projections side by side; ``w_down``: ``[n, F, D]``.  Experts
        ``[first, first + n)`` of the ``E``.
      dtype: the products' operand dtype (``None``: ``x``'s).
      scale: multiplies the normalised weights.
      shared: ``(w_gate_up [D, 2 F_s], w_down [F_s, D])``, an expert
        every token passes through at weight 1, under its own scope; it
        is computed alike by every shard of the experts.

    ``s = sigmoid(x W_g)``, ``S = top_k(s + b)``, ``g_e = scale * s_e /
    (sum_S s + 1e-6)``; ``y = sum_{e in S, held} g_e W_down^e
    (silu(W_gate^e x) * W_up^e x)``, plus the shared expert's output.  Scores, selection and weights are
    float32; ``S`` and the normalisation are over all ``E`` experts, and
    what the experts not held would add is left out.  Every shape is
    static: ``T * per_token`` rows whatever the split, the held pairs
    first, grouped by expert; rows beyond them belong to no group, are
    masked, and on the kernels' path cost no product.

    Returns ``(y [T, D] in x's dtype, aux)``; ``aux`` holds ``selection``
    ``[T, per_token]``, ``expert_rows`` ``[n]`` (rows each held expert
    received), ``pairs_not_held`` and the router's ``scores`` ``[T, E]``.
    """
    t, d = x.shape
    n, k = w_gate_up.shape[0], per_token
    dtype = dtype or x.dtype
    f32 = jnp.float32
    with jax.named_scope(names.SCOPE_MOE_ROUTE):
        scores = jax.nn.sigmoid(jnp.dot(
            x.astype(f32), router_w.astype(f32),
            precision=lax.Precision.HIGHEST))                  # [T, E]
        _, selection = lax.top_k(
            scores + lax.stop_gradient(bias.astype(f32)), k)   # [T, k]
        # the chosen experts' own scores, by a 0/1 product: its transpose
        # is a product too, where a gather's would be a scatter
        chosen = selection[..., None] == jnp.arange(scores.shape[-1])
        gates = (scores[:, None, :] * chosen).sum(-1)
        gates = gates / (gates.sum(-1, keepdims=True) + 1e-6)
        if scale != 1.0:
            gates = gates * scale
        local = selection.T.reshape(-1) - first                # [k * T]
        held = (local >= 0) & (local < n)
        expert = jnp.where(held, local, n)
        # pairs by expert; those not held last
        order = jnp.argsort(expert, stable=True)
        place = jnp.argsort(order)         # its inverse: where each went
        sizes = (expert[:, None] == jnp.arange(n)[None]).sum(
            0, dtype=jnp.int32)                                # [n]
        rows = _rows_to_experts(x.astype(dtype), order, place, held)
    with jax.named_scope(names.SCOPE_MOE_EXPERTS):
        live = (jnp.arange(t * k) < sizes.sum())[:, None]
        gate, up = jnp.split(grouped_dot(
            rows, w_gate_up.astype(dtype), sizes), 2, axis=-1)
        act = jnp.where(live, jax.nn.silu(gate) * up, 0)
        out = grouped_dot(act, w_down.astype(dtype), sizes)
    with jax.named_scope(names.SCOPE_MOE_ROUTE):
        back = _rows_from_experts(out, order, place, held)
        y = (back.reshape(k, t, d) * gates.T[..., None]).sum(0)
    if shared is not None:
        with jax.named_scope(names.SCOPE_MOE_SHARED):
            y = y + _gated_mlp(x.astype(dtype), *shared, dtype)
    aux = {"selection": selection,
           "expert_rows": sizes.astype(f32),
           "pairs_not_held": (t * k - sizes.sum()).astype(f32),
           "scores": scores}
    return y.astype(x.dtype), aux


def _gated_mlp(x, w_gate_up, w_down, dtype):
    """``W_down(silu(W_gate x) * W_up x)``: operands in ``dtype``,
    float32 accumulation and result."""
    dot = lambda a, w: jnp.dot(a, w.astype(dtype),
                               preferred_element_type=jnp.float32)
    gate, up = jnp.split(dot(x, w_gate_up), 2, axis=-1)
    return dot((jax.nn.silu(gate) * up).astype(dtype), w_down)

