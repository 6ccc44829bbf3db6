"""The sizes the chip holds, which are not always the published ones.

A configuration's file carries the source's ``config.json`` under
``published`` and repeats the keys at its top level *as they are run*: a
configuration cut in depth states ``"num_hidden_layers": 12`` there, beside
``published.num_hidden_layers`` 52, and lists the key in ``reduced``.  What
a reader multiplies a device time by, and what the compare builds, is the
held size; ``published`` alone is only what the source says; it stays reachable as
``hp["published"]``, so that a reference handed a share of a layer (32 experts
of 128, a quarter of the vocabulary) knows the router's width or the whole
vocabulary without reading it off a weight's shape.
"""

from __future__ import annotations

from typing import Dict


def held(config: Dict) -> Dict:
    """``published`` with the file's top-level value for each key that
    ``reduced`` lists, plus ``head_dim`` where the source leaves it to
    ``hidden_size / num_attention_heads``, plus the source's own values
    under ``published``.  Any other top-level copy of a
    published key has to equal it: a size changed without a word in
    ``reduced`` is refused, not followed."""
    hp = dict(config["published"])
    reduced = config.get("reduced", [])
    for key in reduced:
        if key not in config:
            raise SystemExit(f"bench: configuration {config.get('name')!r} "
                             f"lists {key!r} in reduced and states no held "
                             f"value for it at its top level")
        hp[key] = config[key]
    changed = sorted(k for k in config["published"]
                     if k in config and k not in reduced
                     and config[k] != config["published"][k])
    if changed:
        raise SystemExit(f"bench: configuration {config.get('name')!r} "
                         f"changes {changed} from the published values and "
                         f"does not list them in reduced")
    if (not hp.get("head_dim") and hp.get("hidden_size")
            and hp.get("num_attention_heads")):
        hp["head_dim"] = hp["hidden_size"] // hp["num_attention_heads"]
    hp["published"] = dict(config["published"])
    return hp
