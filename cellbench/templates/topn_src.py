"""``TopN(Bitmap(frame, rowID=a), frame, n)``: the rows most often set
together with row a. Row a is the mix's key law's draw, kept where it
falls among the class's ``src_rows`` densest rows (a Zipf law over all
rows, drawn again past them, is the same law over those rows). The
answer is the reference's ``topn_src`` (``refs/topn_sparse.py``).

A program without the with-source device leg is refused when this module
is loaded, which the harness does in set-up, before the first read.
"""

from __future__ import annotations

import numpy as np

from ..lib.bytes_fns import SLICE_ROW_BYTES
from ..lib.server import BenchFailure
from ..lib.traffic import Op

SLICE_WIDTH = 1 << 20


def require_device_leg() -> None:
    """Raise ``BenchFailure`` where the program has no with-source TopN
    device leg, whose mark is the ``topnSrc`` stat that
    ``pilosa_tpu.obs.accounting.note_topn_src`` puts on each answer and
    this cell's metrics read. Without it every read walks each row of
    the frame on the host: at ``baseline-c3``'s size a read takes 35-64 s
    under eight clients, so the mix's 256 warm reads alone would run for
    ~1,600-2,100 s, past any run's time limit, before the window began."""
    from pilosa_tpu.obs import accounting
    if not hasattr(accounting, "note_topn_src"):
        raise BenchFailure(
            "the program has no with-source TopN device leg (no"
            " topnSrc stat): its host walk would not finish the warm"
            " reads within a run's time limit")


require_device_leg()


def make(gen, cls: dict) -> Op:
    (row,) = gen.rows(1)
    while row >= int(cls["src_rows"]):
        (row,) = gen.rows(1)
    n = int(cls["n"])
    return Op(cls, (row,), None,
              f'TopN(Bitmap(frame="{gen.frame}", rowID={row}),'
              f' frame="{gen.frame}", n={n})', args={"n": n})


def touching(op: Op, writes: list) -> list:
    return list(writes)     # a write to any row can change a TopN


def answer(ref, op: Op):
    base = ref.topn_src(op.rows[0], op.args["n"])

    def with_writes(writes):
        if writes:
            raise ValueError("topn_src answers no mix with writes")
        return base
    return with_writes


def topn_src_least(op: Op, config: dict) -> int:
    """The source's dense slab once, and for each of the n highest-ranked
    rows and each slice the lesser of its expected bits as 4-byte
    positions and its dense slab: phase 1 reads at least those rows, in
    the smaller of the two forms, whatever program answers."""
    n_cols = int(config["n_cols"])
    k = np.arange(min(op.args["n"], int(config["n_rows"])))
    density = np.minimum(1.0, float(config["d0"])
                         / (k + 1.0) ** float(config["zipf_s"]))
    total = int(config["n_slices"]) * SLICE_ROW_BYTES
    for si in range(int(config["n_slices"])):
        width = min(SLICE_WIDTH, n_cols - si * SLICE_WIDTH)
        total += int(np.minimum(4.0 * density * width,
                                SLICE_ROW_BYTES).sum())
    return total
