"""Shared tile-size selection for the Pallas kernels (docs/DESIGN.md §6).

Two layers:

* ``pick_block`` — the original divisor-only heuristic (largest divisor of
  the dimension no bigger than the preferred MXU-aligned block).  Kept as
  the cold-cache fallback, but no longer used raw by the kernels: for a
  prime dimension just past the preferred block it degrades to block 1 —
  sub-lane tiles that serialize the MXU.
* ``choose_block`` — the production rule: when the best divisor is
  degenerate (less than half the achievable block), keep the preferred
  block and *pad* the dimension up to the next multiple instead.  Every
  kernel wrapper in this package zero-pads its operands to the padded dims
  and slices/masks the result back, so ANY block size is legal — which is
  also what lets the measured autotuner (kernels/autotune.py) search the
  full tile space instead of only divisors.

Tile preferences themselves are resolved through the autotuner's committed
tile file (docs/DESIGN.md §Autotune): ``resolve_tiles`` returns the measured
winner for ``(op, shape, dtype, device_kind)`` when one is cached, and the
caller's heuristic defaults otherwise.  Explicit block arguments at a kernel
call site always win over both.
"""

from __future__ import annotations

from typing import NamedTuple


def pick_block(dim: int, preferred: int) -> int:
    """Largest divisor of ``dim`` that is <= preferred (MXU likes 128s).

    Heuristic fallback only: degrades to 1 on primes.  Kernels go through
    ``choose_block`` which pads instead of shrinking below half the target.
    """
    b = min(preferred, dim)
    while dim % b:
        b -= 1
    return max(b, 1)


class BlockChoice(NamedTuple):
    """A legal (block, padded_dim) pair: ``block`` divides ``padded``, and
    ``padded - dim`` is the zero/masked tail the kernel wrapper adds."""
    block: int
    padded: int

    @property
    def grid(self) -> int:
        return self.padded // self.block


def choose_block(dim: int, preferred: int) -> BlockChoice:
    """Pick a block for ``dim`` targeting ``preferred``, padding if needed.

    If the largest divisor <= preferred is at least half the achievable
    block (min(preferred, dim)), use it unpadded — the common aligned case,
    zero overhead.  Otherwise (prime or near-prime dims) keep the full
    preferred-size block and pad the dimension up to a multiple: padded
    rows/cols are zeros (exact under contraction) and are sliced or
    predicated off by the wrappers, so no sub-lane tile is ever issued.
    """
    if dim <= 0:
        raise ValueError(f"dimension must be positive, got {dim}")
    target = min(max(preferred, 1), dim)
    b = pick_block(dim, preferred)
    if 2 * b >= target:
        return BlockChoice(b, dim)
    return BlockChoice(target, -(-dim // target) * target)


def resolve_tiles(op: str, shape: tuple, dtype, defaults: dict,
                  explicit: dict | None = None) -> dict:
    """Resolve named tile preferences for one kernel call.

    Precedence per tile name: explicit call-site value (not None) >
    autotune-cache winner for ``(op, shape, dtype, device_kind)`` >
    ``defaults``.  Returns a plain dict of ints; callers still pass each
    through ``choose_block`` against the actual dims, so a cached winner
    tuned for one shape family stays legal on any shape.
    """
    out = dict(defaults)
    from repro.kernels.autotune import lookup
    cached = lookup(op, shape, dtype)
    if cached:
        for k in out:
            if k in cached:
                out[k] = int(cached[k])
    if explicit:
        for k, v in explicit.items():
            if v is not None:
                out[k] = int(v)
    return out
