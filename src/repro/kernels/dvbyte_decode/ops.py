"""Jitted public entry point for the Double-VByte decode kernel."""

from __future__ import annotations

from functools import partial

import jax

from .. import registry
from .kernel import DEFAULT_TILE, dvbyte_decode_kernel


@partial(jax.jit, static_argnames=("F", "tile", "interpret"))
def dvbyte_decode_blocks(blocks, start, end, F: int = 4,
                         tile: int = DEFAULT_TILE,
                         interpret: bool | None = None):
    """Decode a batch of B-byte Double-VByte blocks on TPU.

    Drop-in replacement for ``repro.core.device_index.decode_blocks`` (pass
    it as ``decode_fn`` to ``query_step``).  ``interpret=None`` runs the
    kernel body in the Pallas interpreter everywhere but on a TPU.
    """
    if interpret is None:
        interpret = registry.default_interpret()
    return dvbyte_decode_kernel(blocks, start, end, F, tile=tile,
                                interpret=interpret)


def as_decode_fn(F: int = 4, tile: int = DEFAULT_TILE,
                 interpret: bool | None = None):
    """Adapter matching the ``decode_fn(blocks, start, end, F)`` signature."""
    if interpret is None:
        interpret = registry.default_interpret()

    def fn(blocks, start, end, F_):
        return dvbyte_decode_kernel(blocks, start, end, F_, tile=tile,
                                    interpret=interpret)

    return fn


registry.register(registry.KernelSpec(
    name="dvbyte_decode", fn=dvbyte_decode_blocks,
    modes=("conjunctive", "ranked_tfidf", "bm25"),
    description="VMEM-tiled Double-VByte block decode; plug into "
                "device_index.query_step via decode_fn",
    extras={"as_decode_fn": as_decode_fn}))
