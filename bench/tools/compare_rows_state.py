"""``compare_rows.py`` for a configuration whose reference keeps a recurrent
state: the same compare, seed after seed, with the *reference's* state rounded
to another type after every token, the reading ``compare.logits_rtol`` has to
refuse.

    chiprun -- python3 bench/tools/compare_rows_state.py --state-dtype bfloat16 \
        --config solar-open2-250b-ep8 --seeds 4

Every other argument is ``compare_rows.py``'s (``--reference-dtype`` too).
The tool holds no reference and no program of its own: it sets the
reference's ``STATE_DTYPE``.
"""

from __future__ import annotations

import sys

import compare_rows   # beside this file; puts bench/ and the root on the path


def main() -> None:
    at = sys.argv.index("--state-dtype")
    name = sys.argv[at + 1]
    del sys.argv[at:at + 2]
    import jax.numpy as jnp

    from reference import solar_kda

    solar_kda.STATE_DTYPE = jnp.dtype(name)
    compare_rows.main()


if __name__ == "__main__":
    main()
