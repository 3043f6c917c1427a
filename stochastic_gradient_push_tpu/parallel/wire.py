"""Gossip wire codecs: the single encode path for compressed payloads.

Gossip's whole edge over AllReduce is sending less, less often
(GossipGraD's comm-minimization argument, PAPERS.md) — yet the push-sum
round used to ship full-precision payloads, with one ad-hoc ``astype``
cast buried in the collective layer as the only compression.  This
module makes the wire format a first-class, priceable object:

* :class:`WireCodec` — a jit-compatible encode/decode pair applied to
  every *real* payload leaf (``size > 1``) right at the ``ppermute``
  boundary.  ``encode`` returns the tuple of arrays that actually rides
  the interconnect; ``decode`` reconstructs the leaf at the receiver.
  Scalar leaves — the push-sum weight lane — NEVER go through a codec:
  quantizing the de-bias divisor buys no bandwidth and poisons the mass
  accounting every consensus guarantee rests on (the SGPV
  column-stochasticity checks and ``chaos --selftest`` therefore still
  hold under any codec).

* :data:`F32` (identity), :data:`BF16` (truncation), and
  :class:`Int8Codec` — symmetric per-block int8 with float32 scales
  riding alongside the payload (``--wire_block`` elements per scale).
  At the default block of 64 the int8 wire is ``1 + 4/64 = 1.0625``
  bytes/element, a 3.76x payload reduction over f32.

* pricing — :meth:`WireCodec.element_bytes` is what
  ``telemetry/comm.py`` and the planner use to price the *encoded*
  payload (dtype size plus int8 scale overhead), so ``obsreport`` comm
  tables and ``Candidate.priced_cost`` reflect the wire as shipped, not
  a 4 B/element assumption.

Error feedback (the convergence safeguard) lives one layer up: the
collective layer (:func:`..parallel.collectives.gossip_round`) carries a
per-rank residual accumulator that re-injects round ``t``'s quantization
error into round ``t+1``'s send, so compression noise telescopes into a
bounded perturbation instead of a bias.  The codecs here only define the
(de)quantization itself.

The repo-wide invariant enforced by sgplint rule SGPL010: no raw
``.astype`` wire cast on a ``ppermute`` payload outside this module —
every byte the gossip hot path puts on the wire goes through a codec.
"""

from __future__ import annotations

import dataclasses
import math

__all__ = ["WireCodec", "F32Codec", "BF16Codec", "Int8Codec",
           "DecodeSpec", "F32", "BF16", "WIRE_DTYPES",
           "DEFAULT_WIRE_BLOCK", "INT8_SCALE_BYTES", "get_codec",
           "from_comm_dtype"]

WIRE_DTYPES = ("f32", "bf16", "int8")
DEFAULT_WIRE_BLOCK = 64
# dtype of the per-block scale lane riding alongside the int8 payload
INT8_SCALE_BYTES = 4


@dataclasses.dataclass(frozen=True)
class DecodeSpec:
    """In-kernel decode descriptor a codec exposes to the fused gossip
    kernel (ops/gossip_kernel.py): enough static structure — the decode
    kind and the int8 block — for the kernel to reconstruct
    :meth:`WireCodec.decode` chunk-locally in VMEM, with the SAME
    elementwise op order, so the kernel lane stays bit-aligned with the
    XLA ppermute+decode lane.  A codec returning ``None`` (the base
    default for unknown subclasses) keeps the collective layer on the
    XLA path — the kernel never guesses a decode."""

    kind: str                  # "f32" passthrough | "bf16" widen | "int8"
    block: int | None = None   # int8 elements per f32 scale


class WireCodec:
    """Identity/base codec: the payload ships as-is (one wire part).

    Subclasses override :meth:`encode`/:meth:`decode` (traced code — jnp
    only, no host effects) and :meth:`element_bytes` (host pricing).
    ``encode`` must return a *tuple* of arrays; the collective layer
    ppermutes each part and hands the received tuple back to
    :meth:`decode` with the local leaf as the shape/dtype template (all
    ranks hold identically shaped leaves under SPMD).  :meth:`kernel_spec`
    optionally describes the decode to the fused gossip kernel; the base
    ``None`` means "no in-kernel decode known" and pins the XLA path.
    """

    name = "f32"
    lossy = False

    def kernel_spec(self) -> DecodeSpec | None:
        """Static decode descriptor for ops/gossip_kernel.py (None =
        this codec has no in-kernel decode; use the XLA path)."""
        return None

    def encode(self, msg):
        return (msg,)

    def decode(self, wire, like):
        del like
        return wire[0]

    def element_bytes(self, n: int, itemsize: int = 4) -> int:
        """Wire bytes for an ``n``-element leaf of ``itemsize`` storage."""
        return n * itemsize

    def wire_fraction(self, itemsize: int = 4) -> float:
        """Asymptotic encoded-bytes / full-precision-bytes ratio — the
        factor the planner applies to gossip payload-equivalents."""
        n = 1 << 20
        return self.element_bytes(n, itemsize) / float(n * itemsize)

    def to_dict(self) -> dict:
        return {"dtype": self.name}

    def __repr__(self):
        return f"{type(self).__name__}()"


class F32Codec(WireCodec):
    """Explicit name for the identity codec (``--wire_dtype f32``)."""

    def kernel_spec(self) -> DecodeSpec:
        return DecodeSpec("f32")


class BF16Codec(WireCodec):
    """Truncate payloads to bfloat16 on the wire (half the bytes,
    ~1e-3 relative quantization error per round): an astype down before
    the ppermute, an astype back up at the receiver."""

    name = "bf16"
    lossy = True

    def encode(self, msg):
        import jax.numpy as jnp

        return (msg.astype(jnp.bfloat16),)

    def decode(self, wire, like):
        return wire[0].astype(like.dtype)

    def element_bytes(self, n: int, itemsize: int = 4) -> int:
        del itemsize
        return n * 2

    def kernel_spec(self) -> DecodeSpec:
        return DecodeSpec("bf16")


class Int8Codec(WireCodec):
    """Symmetric per-block int8 quantization with f32 scales.

    The flattened leaf is split into ``block``-element blocks; each
    block ships ``round(x / scale)`` as int8 with ``scale =
    max|x| / 127`` riding in a float32 side lane.  Wire cost:
    ``n + 4 * ceil(n / block)`` bytes — 3.76x below f32 at block 64.
    Symmetric (no zero point): gossip payloads are centered parameter
    mixtures, and symmetry keeps ``Q(0) == 0`` exactly, which the
    fault-drop semantics rely on (a masked-to-zero message must ship as
    zero).
    """

    lossy = True

    def __init__(self, block: int = DEFAULT_WIRE_BLOCK):
        if block < 1:
            raise ValueError(f"wire_block must be >= 1, got {block}")
        self.block = int(block)

    @property
    def name(self):
        return "int8"

    def encode(self, msg):
        import jax.numpy as jnp

        n = msg.size
        nb = -(-n // self.block)  # static ceil under jit
        flat = msg.reshape(-1).astype(jnp.float32)
        if nb * self.block != n:
            flat = jnp.pad(flat, (0, nb * self.block - n))
        blocks = flat.reshape(nb, self.block)
        amax = jnp.max(jnp.abs(blocks), axis=1)
        scale = amax / 127.0
        safe = jnp.where(scale > 0.0, scale, 1.0)
        q = jnp.clip(jnp.round(blocks / safe[:, None]),
                     -127.0, 127.0).astype(jnp.int8)
        return (q, scale.astype(jnp.float32))

    def decode(self, wire, like):
        import jax.numpy as jnp

        q, scale = wire
        flat = (q.astype(jnp.float32) * scale[:, None]).reshape(-1)
        return flat[:like.size].reshape(like.shape).astype(like.dtype)

    def element_bytes(self, n: int, itemsize: int = 4) -> int:
        del itemsize
        return n + INT8_SCALE_BYTES * int(math.ceil(n / self.block))

    def kernel_spec(self) -> DecodeSpec:
        return DecodeSpec("int8", block=self.block)

    def to_dict(self) -> dict:
        return {"dtype": "int8", "block": self.block}

    def __repr__(self):
        return f"Int8Codec(block={self.block})"


F32 = F32Codec()
BF16 = BF16Codec()


def get_codec(dtype: str | None,
              block: int = DEFAULT_WIRE_BLOCK) -> WireCodec | None:
    """Resolve a ``--wire_dtype`` flag value into a codec (None for
    unset — the caller-side 'no codec object at all' spelling)."""
    if dtype is None:
        return None
    if dtype == "f32":
        return F32
    if dtype == "bf16":
        return BF16
    if dtype == "int8":
        return Int8Codec(block)
    raise ValueError(f"unknown wire_dtype {dtype!r}; one of {WIRE_DTYPES}")


def from_comm_dtype(comm_dtype) -> WireCodec | None:
    """Map the deprecated ``comm_dtype`` jnp-dtype knob onto a codec."""
    if comm_dtype is None:
        return None
    import jax.numpy as jnp
    import numpy as np

    if np.dtype(comm_dtype) == np.dtype(jnp.bfloat16):
        return BF16
    raise ValueError(
        f"comm_dtype {comm_dtype!r} has no wire codec; use the wire "
        f"API (wire_dtype in {WIRE_DTYPES})")
