"""The paper's 256-bit transfer descriptor (Listing 1) — canonical formats.

Two representations, round-trippable:

1. **Packed host form** — bit-exact with the paper's Listing 1::

       struct descriptor {          // 32 bytes, little-endian
           u32 length;              // transfer length in bytes (<= 4 GiB)
           u32 config;              // front-/backend configuration bits
           u64 next;                // byte address of next descriptor, -1 = end
           u64 source;              // byte address of source
           u64 destination;         // byte address of destination
       }

   Stored as a numpy structured array; used by the cycle simulator, the
   checkpoint manifests and anything that talks "byte addresses".

2. **Device SoA form** (:class:`DescriptorArray`) — a struct-of-arrays of
   int32 *element offsets* into typed JAX buffers. JAX arrays are typed pools,
   not a flat byte space, so on-device descriptors address elements of a named
   (src_pool, dst_pool) pair. ``next`` holds the *index* of the successor
   descriptor in the table (-1 = end-of-chain), which is the natural device
   analogue of the paper's next-pointer. The same class holds NumPy fields
   in its *host form* (:meth:`DescriptorArray.from_host`,
   :meth:`DescriptorArray.to_host`): the runtime keeps a chain there from
   ``submit`` to the drain, which uploads only the index vectors it runs.

Completion tracking follows §II-D: the engine overwrites the first 8 bytes of
a completed descriptor with all-ones (``DONE_SENTINEL``); on device this is a
``done`` flag vector plus the same sentinel written into (length, config).
"""
from __future__ import annotations

import dataclasses
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

# ---------------------------------------------------------------------------
# Constants (paper §II-B / §II-D)
# ---------------------------------------------------------------------------

DESCRIPTOR_BYTES = 32              # 256-bit descriptor
END_OF_CHAIN = np.uint64(0xFFFF_FFFF_FFFF_FFFF)   # `next` == -1 terminates
END_OF_CHAIN_IDX = np.int32(-1)    # device-side successor index sentinel
DONE_SENTINEL32 = np.uint32(0xFFFF_FFFF)          # first 8 B overwritten on done
MAX_TRANSFER_BYTES = 2**32 - 1     # u32 length field -> individual <= 4 GiB

# config field bit layout (frontend low half / backend high half)
CONFIG_IRQ_ENABLE = np.uint32(1 << 0)       # raise IRQ / completion event
CONFIG_WRITEBACK = np.uint32(1 << 1)        # overwrite first 8 B on completion
CONFIG_DECOUPLE_RW = np.uint32(1 << 2)      # backend: decouple R/W channels
CONFIG_SRC_FIXED = np.uint32(1 << 8)        # backend: fixed-address source
CONFIG_DST_FIXED = np.uint32(1 << 9)        # backend: fixed-address destination
CONFIG_BURST_SHIFT = 16                      # backend: max AXI burst length

PACKED_DTYPE = np.dtype(
    [
        ("length", "<u4"),
        ("config", "<u4"),
        ("next", "<u8"),
        ("source", "<u8"),
        ("destination", "<u8"),
    ]
)
assert PACKED_DTYPE.itemsize == DESCRIPTOR_BYTES


# ---------------------------------------------------------------------------
# Packed host form
# ---------------------------------------------------------------------------

def pack(
    length: Sequence[int],
    config: Sequence[int],
    next_addr: Sequence[int],
    source: Sequence[int],
    destination: Sequence[int],
) -> np.ndarray:
    """Build a packed descriptor table (numpy structured array)."""
    length = np.asarray(length, dtype=np.uint64)
    if np.any(length > MAX_TRANSFER_BYTES):
        raise ValueError("descriptor length exceeds u32 field (4 GiB); chain instead")
    out = np.zeros(len(length), dtype=PACKED_DTYPE)
    out["length"] = length.astype(np.uint32)
    out["config"] = np.asarray(config, dtype=np.uint32)
    out["next"] = np.asarray(next_addr, dtype=np.uint64)
    out["source"] = np.asarray(source, dtype=np.uint64)
    out["destination"] = np.asarray(destination, dtype=np.uint64)
    return out


def to_bytes(table: np.ndarray) -> bytes:
    """Serialize a packed table to the exact 32 B/descriptor wire layout."""
    return table.astype(PACKED_DTYPE, copy=False).tobytes()


def from_bytes(raw: bytes) -> np.ndarray:
    if len(raw) % DESCRIPTOR_BYTES:
        raise ValueError(f"raw length {len(raw)} not a multiple of {DESCRIPTOR_BYTES}")
    return np.frombuffer(raw, dtype=PACKED_DTYPE).copy()


def mark_done_packed(table: np.ndarray, idx: int) -> None:
    """§II-D completion writeback: first 8 bytes -> all ones."""
    table["length"][idx] = DONE_SENTINEL32
    table["config"][idx] = DONE_SENTINEL32


def is_done_packed(table: np.ndarray) -> np.ndarray:
    return (table["length"] == DONE_SENTINEL32) & (table["config"] == DONE_SENTINEL32)


# ---------------------------------------------------------------------------
# Device SoA form
# ---------------------------------------------------------------------------

@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class DescriptorArray:
    """Struct-of-arrays descriptor table for on-device execution.

    All fields are int32 vectors of equal length N:
      src    — element offset into the source pool
      dst    — element offset into the destination pool
      length — transfer length in *elements*
      nxt    — successor descriptor index (-1 = end-of-chain)
      config — config bits (same layout as packed form, truncated to 31 bits)
      done   — completion flag (0/1); sentinel mirror of the 8-byte writeback
    """

    src: jax.Array
    dst: jax.Array
    length: jax.Array
    nxt: jax.Array
    config: jax.Array
    done: jax.Array

    # -- pytree protocol ----------------------------------------------------
    def tree_flatten(self):
        return (self.src, self.dst, self.length, self.nxt, self.config, self.done), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    # -- constructors -------------------------------------------------------
    @classmethod
    def create(cls, src, dst, length, nxt=None, config=None) -> "DescriptorArray":
        return cls._build(jnp, src, dst, length, nxt, config)

    @classmethod
    def from_host(cls, src, dst, length, nxt=None,
                  config=None) -> "DescriptorArray":
        """Host form: :meth:`create`'s six int32 fields, as NumPy arrays.

        The runtime plans, packs and drains chains in this form, so none of
        its own bookkeeping crosses to the device and back.
        """
        return cls._build(np, src, dst, length, nxt, config)

    @classmethod
    def _build(cls, xp, src, dst, length, nxt, config) -> "DescriptorArray":
        """The fields as ``xp`` (``jnp`` or ``np``) int32 arrays."""
        src = xp.asarray(src, xp.int32)
        dst = xp.asarray(dst, xp.int32)
        length = xp.asarray(length, xp.int32)
        n = src.shape[0]
        if nxt is None:  # default: sequential chain ending at -1
            nxt = xp.concatenate([xp.arange(1, n, dtype=xp.int32),
                                  xp.full((1,), -1, xp.int32)])
        else:
            nxt = xp.asarray(nxt, xp.int32)
        if config is None:
            config = xp.zeros((n,), xp.int32)
        else:
            config = xp.asarray(config, xp.int32)
        done = xp.zeros((n,), xp.int32)
        return cls(src, dst, length, nxt, config, done)

    @property
    def on_host(self) -> bool:
        """Whether every field is already a NumPy array (host form)."""
        return all(isinstance(f, np.ndarray) for f in self.tree_flatten()[0])

    def to_host(self) -> "DescriptorArray":
        """This chain in host form: itself when :attr:`on_host`, otherwise
        all six fields fetched in one :func:`jax.device_get`."""
        return self if self.on_host else jax.device_get(self)

    @property
    def num_descriptors(self) -> int:
        return self.src.shape[0]

    def mark_done(self, idx) -> "DescriptorArray":
        """Device analogue of the all-ones writeback (in the chain's form)."""
        if self.on_host:
            def put(field, value):
                field = field.copy()
                field[idx] = value
                return field
        else:
            def put(field, value):
                return field.at[idx].set(value)
        return dataclasses.replace(
            self,
            done=put(self.done, 1),
            length=put(self.length, -1),
            config=put(self.config, -1),
        )

    def all_done(self) -> jax.Array:
        return jnp.all(self.done == 1)


def to_packed(
    d: DescriptorArray,
    *,
    elem_bytes: int = 1,
    src_base: int = 0,
    dst_base: int = 0,
    table_base: int = 0,
) -> np.ndarray:
    """Lower an SoA table (either form) to the packed 256-bit host layout.

    Element offsets become byte addresses relative to the given pool bases;
    successor indices become byte addresses of descriptor slots (sequential
    layout at ``table_base``), matching the planner in :mod:`repro.core.chain`.
    """
    src = np.asarray(d.src, np.int64) * elem_bytes + src_base
    dst = np.asarray(d.dst, np.int64) * elem_bytes + dst_base
    length = np.asarray(d.length, np.int64) * elem_bytes
    nxt_idx = np.asarray(d.nxt, np.int64)
    nxt = np.where(
        nxt_idx < 0,
        np.int64(-1),
        table_base + nxt_idx * DESCRIPTOR_BYTES,
    ).astype(np.int64)
    cfg = np.asarray(d.config, np.int64) & 0xFFFF_FFFF
    tab = pack(
        np.where(np.asarray(d.done) == 1, 0, length),  # repacked done entries reset below
        cfg,
        nxt.view(np.uint64) if nxt.dtype == np.uint64 else nxt.astype(np.uint64),
        src.astype(np.uint64),
        dst.astype(np.uint64),
    )
    done = np.asarray(d.done) == 1
    for i in np.nonzero(done)[0]:
        mark_done_packed(tab, int(i))
    return tab


def from_packed(
    table: np.ndarray,
    *,
    elem_bytes: int = 1,
    src_base: int = 0,
    dst_base: int = 0,
    table_base: int = 0,
) -> DescriptorArray:
    """Inverse of :func:`to_packed` (requires aligned addresses)."""
    src = (table["source"].astype(np.int64) - src_base) // elem_bytes
    dst = (table["destination"].astype(np.int64) - dst_base) // elem_bytes
    done = is_done_packed(table)
    length = np.where(done, -1, table["length"].astype(np.int64) // elem_bytes)
    nxt_raw = table["next"]
    nxt = np.where(
        nxt_raw == END_OF_CHAIN,
        np.int64(-1),
        (nxt_raw.astype(np.int64) - table_base) // DESCRIPTOR_BYTES,
    )
    config = np.where(done, -1, table["config"].astype(np.int64))
    d = DescriptorArray.create(src, dst, length, nxt, config)
    return dataclasses.replace(d, done=jnp.asarray(done, jnp.int32))
