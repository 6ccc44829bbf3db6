"""``compare_rows.py`` for ``longcat-flash-omni-ep32``: the same compare, seed
after seed, with the *reference* made wrong in one way, the readings the file's
limits have to refuse.

    chiprun -- python3 bench/tools/compare_rows_longcat.py --fault no_identity \
        --config longcat-flash-omni-ep32 --seeds 2

``--fault``: ``no_identity`` (the identity experts add nothing in the
reference) | ``renormalised`` (the chosen shares divided by their sum) |
``no_scale`` (both latent scale factors left out) (``reference/longcat.py:
FAULT``).  Every other argument is ``compare_rows.py``'s (``--reference-dtype``
too).  The tool holds no reference and no program of its own.
"""

from __future__ import annotations

import sys

import compare_rows   # beside this file; puts bench/ and the root on the path


def main() -> None:
    if "--fault" in sys.argv:
        at = sys.argv.index("--fault")
        from reference import longcat

        longcat.FAULT = sys.argv[at + 1]
        del sys.argv[at:at + 2]
    compare_rows.main()


if __name__ == "__main__":
    main()
