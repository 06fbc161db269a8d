"""The AUTO-input-layout recipe's two jax calls, in one place.

Lower on abstract avals with `auto_input_format()` as `in_shardings`, read
the compiled program's preferred formats with `compiled_input_formats`, and
re-place params leaf-wise — the r5 fix that keeps XLA from copying 7B weight
stacks to its preferred tiling in-program. Both engines and the 7B
benchmarks share these.
"""

from __future__ import annotations

import contextlib

import jax
from jax.experimental.compilation_cache import compilation_cache
from jax.experimental.layout import Format, Layout


def auto_input_format():
    """The in_shardings value requesting compiler-chosen input layouts."""
    return Format(Layout.AUTO)


def compiled_input_formats(compiled):
    """The compiled program's chosen input formats pytree tuple."""
    return compiled.input_formats


def relayout(leaf, fmt):
    """`leaf` re-placed in the compiled program's preferred layout, under
    the sharding it already has. `fmt.sharding` is the executable's own
    spelling of the placement (a SingleDeviceSharding on one chip): taking
    it would turn every NamedSharding-pinned leaf into a differently-named
    equivalent, and programs fed both kinds hand their outputs back in the
    other one — a signature change on every pinned serving program."""
    return jax.device_put(leaf, Format(fmt.layout, leaf.sharding))




@contextlib.contextmanager
def no_persistent_cache():
    """Keep the relayout programs out of JAX's persistent compilation cache.

    `device_put(leaf, Format)` runs a tiny jitted identity whose output has
    the target layout. Served from the persistent cache, that identity came
    back WRONG on the chip: the array carried the requested layout's tag
    over another layout's bytes, `np.asarray` still read it right, and every
    compiled program read the k/v projections wrong (v2 at 36 layers: 0 of
    8 first tokens right with the cache on, 8 of 8 with it off or with the
    cached `jit__identity_fn` entries deleted). Such sub-second programs
    only reach the cache when its compile-time threshold is lowered, but an
    entry once written is read whatever the threshold, so the re-placement
    never asks the cache."""
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def relayout_leaves(leaves: list, fmt_leaves) -> None:
    """Re-place `leaves` IN PLACE in their preferred formats, one at a time
    and each synced before the next starts: live copies stay at one old plus
    one new leaf (a whole-tree device_put holds both layouts — the r5
    2x-residency OOM at 7B). The caller must hold no other reference to the
    old leaves. Placement-time only; never on a serving step."""
    with no_persistent_cache():
        for i, fmt in enumerate(fmt_leaves):
            leaves[i] = relayout(leaves[i], fmt)
            leaves[i].block_until_ready()
