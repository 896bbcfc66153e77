"""The values a remat block keeps: their names, and what they weigh.

A remat block (`core.program.unit(..., remat=True)` run by
`executor._run_remat_group` as one `jax.checkpoint`) recomputes its forward in
the backward pass, all but the values a policy of names keeps
(`jax.checkpoint_policies.save_only_these_names`). `kept(value, name)` is how
a value gets its name: the executor calls it on the op outputs a block was
asked to keep, and an op with residuals of its own calls it on them.

An op whose backward is a `jax.custom_vjp` must name every residual its
forward *rule* makes, inside the rule: the backward reads the values the rule
returned, not the op's output, and one unnamed output of a kernel call remakes
the whole call. A residual that is an input of the rule is kept by a name
where it is made.

`kept` is `jax.ad_checkpoint.checkpoint_name`: an identity that lowers to
nothing where no policy asks for the name.
"""
from __future__ import annotations

import contextlib
from typing import FrozenSet, List

from jax.ad_checkpoint import checkpoint_name


class _Weighed:
    """The asked-for values a remat block's trace named."""

    def __init__(self, names: FrozenSet[str]):
        self.names = names
        self.values = 0
        self.bytes = 0


_WEIGHING: List[_Weighed] = []


def kept(value, name: str):
    """`value` under the name a remat block's policy can keep it by."""
    if _WEIGHING and name in _WEIGHING[-1].names:
        w = _WEIGHING[-1]
        w.values += 1
        w.bytes += value.size * value.dtype.itemsize
    return checkpoint_name(value, name)


@contextlib.contextmanager
def weighing(names):
    """While a remat block that keeps `names` is traced: count the values
    named so and their bytes (what `remat/kept_values` / `remat/kept_bytes`
    say of the block)."""
    w = _Weighed(frozenset(names))
    _WEIGHING.append(w)
    try:
        yield w
    finally:
        _WEIGHING.pop()
