"""Tile-size selection shared by the kernel wrappers and the engine.

One footprint model and one tile rule, so the engine and the kernel entry
points can never disagree on tiling.

The tile rule is the TPU's: a block's second-to-last dimension must be a
multiple of 8 (the sublane count) or the whole array extent.  A row tile
``bb`` is therefore valid for ``B`` ensemble rows when it divides ``B`` and
is a multiple of 8, or when it is ``B`` itself.
"""
from __future__ import annotations

SUBLANES = 8
LANES = 128

#: Scoped VMEM a Pallas kernel may use on a TPU v5e core by default.
SCOPED_VMEM_BYTES = 16 << 20
#: Ring planes of body scratch (rolls, masks, hash state) beyond the pipeline
#: buffers, fitted to the tiles the v5e compiler refused.
_BODY_PLANES = 3
#: Most (rows, 1) columns any kernel pipelines (six stats + two operands).
_COLUMNS = 8


def _round_up(n: int, k: int) -> int:
    return -(-n // k) * k


def valid_tiles(B: int) -> list[int]:
    """Row tiles the chip accepts for ``B`` rows, ascending."""
    tiles = {bb for bb in range(SUBLANES, B + 1, SUBLANES) if B % bb == 0}
    return sorted(tiles | {B})


def pick_divisor_block(B: int, block_b: int) -> int:
    """Largest valid tile of ``B`` rows that is <= ``block_b``.

    Where no valid tile is that small, the smallest valid tile is used: the
    alignment rule outranks the size hint.
    """
    tiles = valid_tiles(B)
    fitting = [bb for bb in tiles if bb <= block_b]
    return fitting[-1] if fitting else tiles[0]


def vmem_bytes(L: int, block_b: int, *, in_kernel_bits: bool = False) -> int:
    """Scoped VMEM the chip's compiler asks for one kernel tile of rings.

    Arrays are laid out in (8, 128) tiles, so rows round up to 8 and ring
    sites (plus the two halo columns of ``pdes_step``) to 128 lanes; a
    (rows, 1) column fills whole lanes.  Pallas double-buffers every
    pipelined block: the tau tile in and out, the two event-word planes when
    they are streamed (``pdes_step``; ``pdes_multistep_counter`` generates
    them in-kernel), and up to eight columns (six stats, Δ, trial/GVT).  On
    top, the compiler keeps ``_BODY_PLANES`` ring planes of body scratch.
    The model is conservative: tiles it rejects sometimes compile, but every
    tile the v5e compiler refused in calibration is rejected.
    """
    rows = _round_up(block_b, SUBLANES)
    plane = rows * _round_up(L + 2, LANES) * 4
    column = rows * LANES * 4
    pipelined = 2 * (2 if in_kernel_bits else 4)
    return (pipelined + _BODY_PLANES) * plane + 2 * _COLUMNS * column


def pick_vmem_block(B: int, L: int, *, budget: int = SCOPED_VMEM_BYTES,
                    in_kernel_bits: bool = False) -> int:
    """Largest valid row tile of ``B`` whose footprint fits ``budget``.

    Raises:
      ValueError: no valid tile fits (rings too long for one tile's VMEM).
    """
    fitting = [bb for bb in valid_tiles(B)
               if vmem_bytes(L, bb, in_kernel_bits=in_kernel_bits) <= budget]
    if not fitting:
        smallest = valid_tiles(B)[0]
        raise ValueError(
            f"no valid kernel tile for B={B} rings of L={L}: the smallest "
            f"tile ({smallest} rows) needs "
            f"{vmem_bytes(L, smallest, in_kernel_bits=in_kernel_bits)} "
            f"bytes of VMEM, budget {budget}")
    return fitting[-1]
