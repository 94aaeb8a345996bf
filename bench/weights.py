"""What every model family's random weights share: the key of a seed and
the nesting of named leaves into a tree.

The weights themselves come from the configuration's family module
(`bench/families/`, see `bench.spec.family`).  Both sides of the
comparison make them there: the harness through the family's
`program_params` for the program, which packs them to its weight plane;
the reference one layer at a time, applying its own reading of the plane.
"""
from __future__ import annotations

import jax


def root_key(seed: int):
    """A key for any whole-number seed, including ones past 32 bits."""
    seed = int(seed) % 2 ** 64
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def nest(pairs) -> dict:
    """(name tuple, value) pairs -> a nested dict, in the pairs' order."""
    out: dict = {}
    for name, value in pairs:
        node = out
        for part in name[:-1]:
            node = node.setdefault(part, {})
        node[name[-1]] = value
    return out
