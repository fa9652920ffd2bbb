#!/usr/bin/env python3
"""``calibrate.py`` with every reference run in chunks of one client
and test blocks of one sequence.

    python3 benchmarks/fedbench/calibrate_chunk1.py \
        --workload dsv2lite_fedbwo_4silos --seeds 101-102 \
        --control 101-102 --faults 101-102 [--out FILE]

Takes ``calibrate.py``'s arguments and prints its readings.  The
reference sizes its client chunks from the program at one client
(``Reference._fit_count``), but keeps the previous chunk's weights live
while the next chunk runs, which that estimate does not count; at batch
1 the half-batch fault's program, which trains on no sequence, also
estimates less scratch than a sound client needs.  On
``dsv2lite_fedbwo_4silos`` (2.14 GB a weight copy) the estimate allows
two clients a chunk and the chip runs out of memory.  The chunking
changes only the order of the reference's sums, not what it computes.
"""
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))
sys.path.insert(0, str(HERE.parents[1] / "src"))

from fedbench import calibrate                                # noqa: E402
from fedbench.reference import Reference                      # noqa: E402


def _one_client(fit):
    def capped(self, lower, budget, counts):
        return min(fit(self, lower, budget, counts), 1)
    return capped


if __name__ == "__main__":
    Reference._fit_count = _one_client(Reference._fit_count)
    sys.exit(calibrate.main())
