"""StitchedArena: the JAX-side memory lake.

One pre-reserved HBM buffer of 2 MB chunks, managed by the GMLake allocator
(host-side metadata) and accessed through the stitch kernels (device-side
data movement). This is the TPU materialisation of the paper's design: the
allocator decides *which* chunks back a logical tensor; the extent table /
chunk map carries that decision to the DMA engine.

Everything is functional: ``store``/``load`` return new buffers / arrays and
the caller (or the ``Arena`` convenience wrapper) threads the buffer state.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..alloc import registry
from ..alloc.caching_allocator import Allocation
from ..alloc.chunks import CHUNK_SIZE, VMMDevice
from ..kernels import ops
from .trace import TraceRecorder


#: lanes of one TPU vreg row; every chunk's minor dimension is a multiple
LANE = 128


@dataclass(frozen=True)
class ArenaConfig:
    n_chunks: int
    dtype: jnp.dtype = jnp.bfloat16
    #: interpret=True runs the Pallas kernels in Python (CPU validation)
    interpret: bool = False
    #: run the pure-jnp reference ops instead of the kernels
    use_reference_ops: bool = False
    #: device shape of one chunk, ``(rows, lanes)`` with lanes a multiple of
    #: 128. Default: the whole 2 MB lane-dense. A token-structured tenant
    #: (the KV cache) passes its own; the chunk's unused tail (CHUNK_SIZE
    #: minus rows*lanes*itemsize bytes) is never materialised on device.
    chunk_shape: Optional[Tuple[int, int]] = None

    @property
    def itemsize(self) -> int:
        return jnp.dtype(self.dtype).itemsize

    @property
    def chunk_dims(self) -> Tuple[int, int]:
        if self.chunk_shape is None:
            return (CHUNK_SIZE // self.itemsize // LANE, LANE)
        rows, lanes = self.chunk_shape
        if lanes % LANE or rows * lanes * self.itemsize > CHUNK_SIZE:
            raise ValueError(f"chunk shape {self.chunk_shape} is not lane-dense "
                             f"within {CHUNK_SIZE} bytes")
        return (rows, lanes)

    @property
    def chunk_elems(self) -> int:
        """Usable elements per chunk."""
        rows, lanes = self.chunk_dims
        return rows * lanes

    @property
    def capacity_bytes(self) -> int:
        return self.n_chunks * CHUNK_SIZE


class Arena:
    """Allocator backend + device buffer + stitch-kernel access paths.

    ``allocator`` is backend-generic: a ``repro.alloc`` registry key
    (default ``"gmlake"``), an already-constructed backend instance, or
    None. Host-side allocation accounting (``alloc_elems``/``free``/
    metrics) works with every backend; the device data-movement paths
    (``chunk_map``/``store``/``load``) additionally require the backend's
    blocks to carry chunk ``extents`` — i.e. a stitching backend — because
    the Pallas kernels address physical chunks, not virtual offsets.
    """

    def __init__(self, config: ArenaConfig, allocator=None,
                 recorder: Optional[TraceRecorder] = None):
        self.config = config
        if allocator is None:
            allocator = "gmlake"
        if isinstance(allocator, str):
            self.device_model = VMMDevice(config.capacity_bytes)
            self.allocator = registry.create(allocator, self.device_model)
        else:
            self.device_model = allocator.device
            self.allocator = allocator
        self.recorder = recorder
        self.buf = jnp.zeros((config.n_chunks,) + config.chunk_dims, config.dtype)
        self._trace_ids: Dict[int, int] = {}

    # ------------------------------------------------------------------
    # allocation (host metadata only)
    # ------------------------------------------------------------------
    def alloc_elems(self, n_elems: int, label: str = "") -> Allocation:
        # bytes the elements occupy, counting each chunk's unused tail
        nbytes = -(-int(n_elems) * CHUNK_SIZE // self.config.chunk_elems)
        alloc = self.allocator.malloc(max(nbytes, CHUNK_SIZE))
        if self.recorder is not None:
            self._trace_ids[id(alloc)] = self.recorder.alloc(alloc.req_size, label)
        return alloc

    def free(self, alloc: Allocation) -> None:
        self.allocator.free(alloc)
        if self.recorder is not None:
            self.recorder.free(self._trace_ids.pop(id(alloc)))

    def require_stitching(self) -> None:
        """Fail loudly when a device data path is used with a backend whose
        blocks carry no chunk extents (capabilities.stitching is False)."""
        caps = getattr(type(self.allocator), "capabilities", None)
        if caps is None or not caps.stitching:
            raise TypeError(
                f"arena data movement needs a stitching backend whose blocks "
                f"carry chunk extents; {self.allocator.name!r} is "
                f"accounting-only here (alloc_elems/free/metrics still work)"
            )

    def chunk_map(self, alloc: Allocation, pad_to: Optional[int] = None) -> jax.Array:
        self.require_stitching()
        return ops.chunk_map_from_extents(alloc.block.extents, pad_to=pad_to)

    # ------------------------------------------------------------------
    # data movement (device)
    # ------------------------------------------------------------------
    def _ops(self):
        c = self.config
        if c.use_reference_ops:
            return ops.gather_ref, ops.scatter_ref
        gather = lambda a, m: ops.gather(a, m, interpret=c.interpret)  # noqa: E731
        scatter = lambda a, m, v: ops.scatter(a, m, v, interpret=c.interpret)  # noqa: E731
        return gather, scatter

    def store(self, alloc: Allocation, array: jax.Array) -> None:
        """Write a logical tensor into the allocation's chunks."""
        c = self.config
        flat = array.astype(c.dtype).reshape(-1)
        n_chunks = -(-flat.size // c.chunk_elems)
        cmap = self.chunk_map(alloc)
        assert n_chunks <= cmap.shape[0], (
            f"tensor needs {n_chunks} chunks, allocation has {cmap.shape[0]}"
        )
        pad = n_chunks * c.chunk_elems - flat.size
        if pad:
            flat = jnp.pad(flat, (0, pad))
        _, scatter = self._ops()
        self.buf = scatter(self.buf, cmap[:n_chunks],
                           flat.reshape((n_chunks,) + c.chunk_dims))

    def load(self, alloc: Allocation, shape: Tuple[int, ...], dtype=None) -> jax.Array:
        """Read a logical tensor back out of the allocation's chunks."""
        c = self.config
        n_elems = int(np.prod(shape))
        n_chunks = -(-n_elems // c.chunk_elems)
        cmap = self.chunk_map(alloc)[:n_chunks]
        gather, _ = self._ops()
        flat = gather(self.buf, cmap).reshape(-1)[:n_elems]
        out = flat.reshape(shape)
        return out.astype(dtype) if dtype is not None else out

    # ------------------------------------------------------------------
    # introspection
    # ------------------------------------------------------------------
    @property
    def reserved_bytes(self) -> int:
        return self.allocator.reserved_bytes

    @property
    def active_bytes(self) -> int:
        return self.allocator.stats.active_bytes

    @property
    def utilization(self) -> float:
        return self.allocator.stats.utilization
