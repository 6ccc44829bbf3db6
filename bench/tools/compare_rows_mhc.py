"""``compare_rows.py`` for a configuration whose reference normalises a mixing
matrix: the same compare, seed after seed, with the *reference* doing another
number of Sinkhorn normalisations than the configuration states, the reading
``compare.logits_rtol`` has to refuse.

    chiprun -- python3 bench/tools/compare_rows_mhc.py --sinkhorn-iters 3 \
        --config xing4.0-29b-a4b-stage --seeds 4

Every other argument is ``compare_rows.py``'s (``--reference-dtype`` too).
The tool holds no reference and no program of its own: it hands the
reference's ``forward`` an ``hp`` with ``hc_sinkhorn_iters`` replaced.
"""

from __future__ import annotations

import sys

import compare_rows   # beside this file; puts bench/ and the root on the path


def main() -> None:
    at = sys.argv.index("--sinkhorn-iters")
    iters = int(sys.argv[at + 1])
    del sys.argv[at:at + 2]
    from reference import xing_mhc

    forward = xing_mhc.forward
    xing_mhc.forward = lambda params, hp, *rest, **more: forward(
        params, dict(hp, hc_sinkhorn_iters=iters), *rest, **more)
    compare_rows.main()


if __name__ == "__main__":
    main()
