"""Find the knee of the K-EXAONE serving cell once, on the chip:
`tools/sweep_docs.py`'s sweep (every rate a window of its own, drained
at the close, all in one process over one set of seeded weights) for the
cell whose traffic names `drivers/serve_exaone.py`. That tool builds its
driver by the traffic file's name for it and asks it only for
`build_model`, so this one is its `main` under the cell's own name.

    python3 benchmark/tools/sweep_mixed.py \
        --workload k-exaone-236b-a23b.serve-mixed \
        --rates 3,4,5,6,7.5,9 --seed 1 --seconds 45

One JSON line a rate. The knee is the highest rate whose first tokens
are no later in the second half of the window than in the first; the
cell runs at 1.5 x that, and the traffic file's `rate_from` keeps the
readings.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.tools import sweep_docs    # noqa: E402

if __name__ == "__main__":
    sweep_docs.main()
