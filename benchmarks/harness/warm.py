"""Programs a cell loads in set-up so that none compiles inside its
window.

The reader (client/ec_reader.py) decides which decode shapes it asks
for: a straggler past its hedge delay has its one cell decoded from
parity at batch width 1 (`_decode_cell_traced`), and the rest of the
read is replanned as ONE batched decode around the stragglers at the
decode width, one program for each count of units recovered
(`_recover_into`). A program is per shape, not per erasure pattern, so
one pattern of each shape loads it.
"""

from __future__ import annotations

import numpy as np


def reader_decode_shapes(scheme: dict) -> list[tuple[int, int]]:
    """(batch width, units recovered) of every decode the reader can
    ask for at `scheme`: width 1 for the hedge's one cell, the decode
    width for 1 to p units."""
    from ozone_tpu.codec.pipeline import decode_batch_size

    return [(1, 1)] + [(decode_batch_size(), e)
                       for e in range(1, scheme["p"] + 1)]


def decoders(scheme: dict, shapes: list[tuple[int, int]]) -> None:
    """Load the fused decoder of each (width, units recovered) in
    `shapes` at `scheme` (k, p, codec, cell, bpc)."""
    from ozone_tpu.codec import fused
    from ozone_tpu.codec.api import CoderOptions
    from ozone_tpu.utils.checksum import ChecksumType

    s = scheme
    spec = fused.FusedSpec(
        CoderOptions(s["k"], s["p"], s["codec"], cell_size=s["cell"]),
        ChecksumType.CRC32C, s["bpc"])
    for width, e in shapes:
        valid = list(range(e, e + s["k"]))
        out = fused.make_fused_decoder(spec, valid, list(range(e)))(
            np.zeros((width, s["k"], s["cell"]), dtype=np.uint8))
        np.asarray(out[0])
