"""``compare_rows.py`` for ``olmo-hybrid-7b-stage``: the same compare, seed after seed,
with the *reference* made wrong in one way, the readings
``compare.logits_rtol`` has to refuse (or, for the state's type, is reported
to see or not to see).

    chiprun -- python3 bench/tools/compare_rows_olmo.py --state-dtype bfloat16 \
        --config olmo-hybrid-7b-stage --seeds 4
    chiprun -- python3 bench/tools/compare_rows_olmo.py --fault beta_not_doubled \
        --config olmo-hybrid-7b-stage --seeds 2

``--state-dtype``: the reference's recurrent state rounded to that type after
every token.  ``--fault``: ``beta_not_doubled`` | ``decay_a_channel`` |
``norm_before`` (``reference/olmo_hybrid.py: FAULT``).  Every other argument is
``compare_rows.py``'s (``--reference-dtype`` too).  The tool holds no
reference and no program of its own.
"""

from __future__ import annotations

import sys

import compare_rows   # beside this file; puts bench/ and the root on the path


def _take(flag: str):
    if flag not in sys.argv:
        return None
    at = sys.argv.index(flag)
    value = sys.argv[at + 1]
    del sys.argv[at:at + 2]
    return value


def main() -> None:
    state, fault = _take("--state-dtype"), _take("--fault")
    import jax.numpy as jnp

    from reference import olmo_hybrid

    if state:
        olmo_hybrid.STATE_DTYPE = jnp.dtype(state)
    olmo_hybrid.FAULT = fault
    compare_rows.main()


if __name__ == "__main__":
    main()
