"""Block shapes of the expert kernels, chosen from their operand shapes
under a VMEM budget (DESIGN.md §5).

The sorted layout gives every BLK-row block one expert, and an expert's
blocks are consecutive. When a kernel's weight block (esmm, esffn) or dW
block (esfk) spans the whole expert, its block index stays the same over
that expert's row blocks, so the Pallas pipeline fetches (or writes back)
it once per run of blocks instead of once per grid step, and the grid has
one step per row block. The rule takes that whole-expert block wherever
its footprint fits ``VMEM_BUDGET``; otherwise it takes the largest
128-multiples that divide the dimensions and fit. The row block stays the
layout's ``blk``: ``block_expert`` is defined per ``blk`` rows.

Each kernel module describes its own footprint next to its BlockSpecs
(``*_blocks``); this module holds what they share: the candidates along
one axis, the choice, the padded size of a VMEM buffer and the
``vmem_limit_bytes`` a footprint asks for.
"""
from __future__ import annotations

import itertools
import math
from typing import Callable, Sequence

from repro.common import round_up

#: TPU v5e: 128 MiB of VMEM per TensorCore (the one chip kind the Pallas
#: kernels target; Google Cloud documentation, TPU v5e).
VMEM_BYTES = 128 * 2**20
#: What one call's buffers may take; the rest is left to Mosaic's own
#: scratch and to the temporaries the footprint does not see.
VMEM_BUDGET = 64 * 2**20
LANE = 128

#: One VMEM buffer: (block shape, itemsize).
Buffer = tuple[tuple[int, ...], int]


def candidates(dim: int, tile: int = 1) -> list[int]:
    """Block sizes along one axis, largest first: the whole axis, then
    the 128-multiples below it that divide it. ``tile`` (a quantization
    tile along the axis) must divide every candidate."""
    out = [dim] + [
        b for b in range((dim - 1) // LANE * LANE, 0, -LANE)
        if dim % b == 0
    ]
    return [b for b in out if b % tile == 0] or [dim]


def grid_steps(dims: Sequence[int], blocks: Sequence[int]) -> int:
    return math.prod(d // b for d, b in zip(dims, blocks))


def choose(
    dims: Sequence[int],
    footprint: Callable[[tuple[int, ...]], int],
    *,
    tiles: Sequence[int] | None = None,
) -> tuple[int, ...]:
    """The blocks over ``dims`` with the fewest grid steps whose
    ``footprint`` fits ``VMEM_BUDGET``; of equal step counts, the one with
    the larger leading block. Where nothing fits, the smallest blocks (the
    compile then says by how much they miss)."""
    tiles = tiles or (1,) * len(dims)
    options = list(itertools.product(
        *(candidates(d, t) for d, t in zip(dims, tiles))))
    fits = [b for b in options if footprint(b) <= VMEM_BUDGET]
    if not fits:
        return options[-1]
    return min(fits, key=lambda b: (grid_steps(dims, b), [-x for x in b]))


def buffer_bytes(shape: Sequence[int], itemsize: int) -> int:
    """VMEM bytes of one buffer: the last two dims padded to the
    (sublane, 128) tile of the dtype (8 rows of 32 bits, packed for
    narrower types)."""
    *lead, rows, cols = (1,) * max(0, 2 - len(shape)) + tuple(shape)
    sub = 8 * max(1, 4 // itemsize)
    return (math.prod(lead) * round_up(rows, sub) * round_up(cols, LANE)
            * itemsize)


def vmem_bytes(blocks: Sequence[Buffer], scratch: Sequence[Buffer]) -> int:
    """Footprint of one call: every pipelined block twice (the pipeline
    double-buffers), scratch and in-kernel temporaries once."""
    return (2 * sum(buffer_bytes(*b) for b in blocks)
            + sum(buffer_bytes(*b) for b in scratch))


def vmem_limit(footprint: int) -> int:
    """``vmem_limit_bytes`` for a call of this footprint: half again for
    what the footprint does not see, and never over the chip's VMEM."""
    return min(VMEM_BYTES, round_up(footprint * 3 // 2, 2**20) + 4 * 2**20)
