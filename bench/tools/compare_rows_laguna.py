"""``compare_rows.py`` for ``laguna-xs.2-ep2``: the same compare, seed after
seed, with the *reference* made wrong in one way, the readings the file's
limits have to refuse.

    chiprun -- python3 bench/tools/compare_rows_laguna.py --fault whole_context \
        --config laguna-xs.2-ep2 --seeds 2

``--fault``: ``whole_context`` (the window's mask left out of the reference: a
window layer attends every earlier position) | ``no_gate`` | ``rotate_all``
(``reference/laguna.py: FAULT``).  Every other argument is ``compare_rows.py``'s
(``--reference-dtype`` too).  The tool holds no reference and no program of
its own.
"""

from __future__ import annotations

import sys

import compare_rows   # beside this file; puts bench/ and the root on the path


def main() -> None:
    if "--fault" in sys.argv:
        at = sys.argv.index("--fault")
        from reference import laguna

        laguna.FAULT = sys.argv[at + 1]
        del sys.argv[at:at + 2]
    compare_rows.main()


if __name__ == "__main__":
    main()
