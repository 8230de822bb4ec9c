"""Row-strip planner for the conv kernels (DESIGN.md §3, §6).

The paper's persistent design streams feature maps through fixed on-chip
buffers; the Pallas analogue bounds the per-grid-cell VMEM working set by
tiling the conv over *row strips with a k−1-row halo* instead of parking
one whole padded image in VMEM.  The grid grows from `(N, c_out/bn)` to
`(N, n_strips, c_out/bn)`, throughput becomes independent of image
height, and each cell holds only

    x slab   (slab_h, Wp, c_in) int8,  slab_h = (strip_h−1)·stride + k
    weights  one c_out tile of constant codes (dense or bitmap-packed)
    acc/y    (strip_h·w_out, bn) int32 / f32 (+ shortcut f32 if present)

Strip s reads padded input rows `[s·strip_h·stride, s·strip_h·stride +
slab_h)` — consecutive strips overlap by the `k − stride` halo rows — and
owns output rows `[s·strip_h, (s+1)·strip_h)`.  Because every output row
depends only on input rows inside its strip's slab, the tiled conv is
bit-identical to the untiled one by construction; the last strip may run
past `h_out` (the caller pads the input with zero rows, exact for int8)
and its surplus rows are masked out of the on-chip amax and sliced off
after the launch.

``plan_strips`` picks the largest ``strip_h`` whose working set fits a
VMEM budget; 7×7-map layers (conv5_x) degenerate to a single strip, i.e.
exactly the pre-tiling kernel.

On the chip a strided conv reads its input as stride² *phases*
(``tap_phases``): phase (ry, rx) holds padded pixels (i·s+ry, j·s+rx),
so every tap becomes a stride-1 window of one phase and a strip's slab
is ``strip_h + halo`` phase rows, ``halo = (k-1)//stride``.
"""
from __future__ import annotations

import dataclasses
import math

# Per-grid-cell VMEM budget, counted as Mosaic lays the blocks out (see
# ``vmem_bytes``): inputs and outputs double-buffered, plus the kernel's
# accumulator-sized temporaries.  v5e's default scoped VMEM limit is
# 16 MiB; 6 MiB leaves the compiler room for its own scratch.
DEFAULT_VMEM_BUDGET = 6 << 20


def vmem_bytes(shape, itemsize: int) -> int:
    """Bytes one VMEM block of ``shape`` occupies on the chip: the last
    two dims pad to whole (32/itemsize, 128) tiles — (32, 128) for int8,
    (8, 128) for f32/int32."""
    *lead, r, c = (1,) * (2 - len(shape)) + tuple(shape)
    sub = 32 // itemsize
    return (math.prod(lead) * -(-r // sub) * sub * -(-c // 128) * 128
            * itemsize)


def tap_phases(k: int, stride: int) -> tuple:
    """The (row, col) phases of a stride-``stride`` input that the k×k taps
    read, in the order the kernels stack them: tap (dy, dx) reads phase
    (dy % stride, dx % stride) at phase offset (dy // stride, dx //
    stride).  All stride² phases when k >= stride; only (0, 0) for a
    strided 1×1."""
    return tuple(sorted({(dy % stride, dx % stride)
                         for dy in range(k) for dx in range(k)}))


@dataclasses.dataclass(frozen=True)
class StripPlan:
    """Static row-strip geometry (plus working-set accounting) for one
    conv launch.  The kernels read the phase geometry (``halo``,
    ``ph_rows``); the raw padded-input view of the same strips
    (``slab_h``, ``row_step``, ``x_rows``) is derived for the strip-looped
    jnp oracles."""

    k: int
    stride: int
    w_out: int
    strip_h: int     # output rows per strip
    n_strips: int    # ceil(h_out / strip_h)
    x_bytes: int = 0     # VMEM bytes of the double-buffered x slab
    cell_bytes: int = 0  # VMEM bytes of every block + temporaries per cell

    @property
    def halo(self) -> int:
        """Extra phase rows/cols a strip reads: (k-1)//stride."""
        return (self.k - 1) // self.stride

    @property
    def ph_rows(self) -> int:
        """Rows of each input phase the kernels read overall."""
        return self.n_strips * self.strip_h + self.halo

    @property
    def ms(self) -> int:
        """Output elements per strip."""
        return self.strip_h * self.w_out

    @property
    def ms_pad(self) -> int:
        """``ms`` rounded up to the f32 sublane multiple (8)."""
        return -(-self.ms // 8) * 8

    @property
    def slab_h(self) -> int:
        """Padded-input rows one strip's taps read."""
        return (self.strip_h - 1) * self.stride + self.k

    @property
    def row_step(self) -> int:
        """Padded-input rows between consecutive strips."""
        return self.strip_h * self.stride

    @property
    def x_rows(self) -> int:
        """Padded-input rows every strip's slab covers."""
        return (self.n_strips - 1) * self.row_step + self.slab_h


def strip_geometry(*, k: int, stride: int, h_out: int, w_out: int,
                   strip_h: int) -> StripPlan:
    """Pure strip geometry for a given strip_h (no budget accounting) —
    what the Pallas kernels and the strip-looped jnp lowering share."""
    strip_h = max(1, min(strip_h, h_out))
    return StripPlan(k=k, stride=stride, w_out=w_out, strip_h=strip_h,
                     n_strips=-(-h_out // strip_h))


def plan_strips(*, k: int, stride: int, h_out: int, w_out: int,
                c_in: int, bn: int, weight_bytes: int,
                has_shortcut: bool = False,
                budget: int = DEFAULT_VMEM_BUDGET,
                strip_h: int | None = None) -> StripPlan:
    """Pick output-rows-per-strip from the VMEM budget.

    Cell working set, as the chip lays it out (``vmem_bytes``), with every
    pipelined block double-buffered: the int8 x slab — one
    ``(strip_h + halo, w_out + halo, c_in)`` block per input phase
    (``tap_phases``) — the ``weight_bytes`` of one c_out tile of
    constant codes (the caller's tiled single-buffer size: dense, packed
    or depthwise), the f32 y tile and — when present — the f32 shortcut
    tile, plus single copies of the int32 accumulator, one tap's int32
    product and the f32 epilogue value.  Returns the largest
    ``strip_h ≤ h_out`` that fits, degenerating to one strip when the
    whole image fits (7×7 maps) and to single-row strips when even those
    exceed the budget.  ``strip_h`` overrides the search (tests /
    benchmarks force awkward strip boundaries).
    """
    n_ph = len(tap_phases(k, stride))

    def plan_of(sh: int) -> StripPlan:
        g = strip_geometry(k=k, stride=stride, h_out=h_out, w_out=w_out,
                           strip_h=sh)
        x_b = 2 * n_ph * vmem_bytes((sh + g.halo, w_out + g.halo, c_in), 1)
        tile = vmem_bytes((g.ms_pad, bn), 4)
        blocks = 2 * (weight_bytes + tile * (2 if has_shortcut else 1)
                      + 3 * vmem_bytes((1, bn), 4))   # scale, bias, amax
        temps = 3 * tile + vmem_bytes((g.ms_pad, c_in), 1)
        return dataclasses.replace(g, x_bytes=x_b,
                                   cell_bytes=x_b + blocks + temps)

    if strip_h is not None:
        return plan_of(strip_h)
    best = plan_of(1)
    for sh in range(2, h_out + 1):
        cand = plan_of(sh)
        if cand.cell_bytes > budget:
            break
        best = cand
    return best
