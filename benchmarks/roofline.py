"""Operations and bytes of the algorithm, counted from shapes and trees.

Kept with the benchmark so that a PR that changes a kernel cannot change
how its work is counted.  Peaks are in ``peaks.json`` keyed by
``device_kind``; a device that is not there is an error.
"""

from __future__ import annotations

import json
import os
import re
from typing import Dict, Optional, Tuple

_HERE = os.path.dirname(os.path.abspath(__file__))


def peaks_for(device_kind: str) -> Dict[str, float]:
    with open(os.path.join(_HERE, "peaks.json")) as fh:
        table = json.load(fh)
    if device_kind not in table or device_kind == "source":
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       "benchmarks/peaks.json: add it with its source")
    return table[device_kind]


def hist_pass_work(rows: int, features: int, bins: int, slots: int,
                   values: int = 3) -> Tuple[float, float]:
    """(operations, bytes) of one histogram pass.

    The pass sums ``values`` numbers (gradient, hessian, count) of each of
    ``rows`` rows into one of ``bins`` bins per feature, for each of
    ``slots`` leaf slots at once.  On a matrix unit that is the product of
    the rows' one-hot bin matrix (features*bins x rows) with their values
    spread over the slots (rows x values*slots):

        operations = 2 * rows * features * bins * values * slots

    counted once, however many products the kernel makes of it (a bf16
    hi+lo pass runs two and is credited with one).  Bytes are what the pass
    has to read and write once: a byte of bin per row and feature, the
    row's ``values`` float32 numbers and its int32 slot, and the float32
    result:

        bytes = rows * (features + 4 * values + 4)
                + slots * features * bins * values * 4
    """
    ops = 2.0 * rows * features * bins * values * slots
    byts = (rows * (features + 4.0 * values + 4.0)
            + 4.0 * slots * features * bins * values)
    return ops, byts


_SHAPE = re.compile(r"(u8|s8|s32|f32|bf16)\[([0-9,]+)\]")


def hist_call_shapes(long_name: str, bins: int) -> Optional[Dict[str, int]]:
    """Shapes of one ``hist_leaves_pallas`` call from its HLO text.  The
    result is ``f32[n, values*slots, block_features*bins]``: a call sums
    one block of features (the last dimension's lanes), whatever ``n``
    shows (1 where the call owns its result, the pass's number of blocks
    where XLA lets the calls of a pass write their slices of one buffer).
    Slots are the stored ones, a multiple of 8; ``rows`` is the u8
    operand's, where the text shows it."""
    head, _, tail = long_name.partition("custom-call(")
    res = _SHAPE.search(head.split(" = ", 1)[-1])
    if not res:
        return None
    out = [int(x) for x in res.group(2).split(",")]
    if len(out) != 3:
        return None
    shapes = {"features": out[2] // bins, "slots": out[1] // 3}
    u8 = [m for m in _SHAPE.finditer(tail) if m.group(1) in ("u8", "s8")]
    if u8:
        shapes["rows"] = int(u8[0].group(2).split(",")[0])
    return shapes


def necessary_bytes(tree_counts, features: int, bin_bytes: int = 1,
                    value_bytes: int = 8) -> float:
    """Bytes a histogram GBDT cannot avoid reading to grow one tree,
    whatever implements it: every row at the root, and at each split the
    rows of the smaller child (the larger child's histogram comes by
    subtraction), each row costing its features' bins plus gradient and
    hessian.  ``tree_counts`` is (root_rows, [(left_rows, right_rows), ...])
    read from the finished tree's own node counts."""
    root_rows, splits = tree_counts
    rows = float(root_rows) + sum(min(l, r) for l, r in splits)
    return rows * (features * bin_bytes + value_bytes)
