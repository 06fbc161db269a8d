"""Recompile detector.

jax.jit keys its executable cache on the (shape, dtype, sharding,
committed-ness) signature of every input leaf. A signature the program has
not seen before means a FULL recompile — measured at ~3.5 s per serving
program on the 470m model (Round-4: unpinned cache leaves silently
recompiled the v2 serving programs on every admission wave). The detector
mirrors that cache key at dispatch time: fingerprint the arguments, count
signatures per program name, and warn LOUDLY when a *pinned* program (one
whose signature is supposed to be stable, i.e. every serving program) sees
a new one.

This is an observer, not a guard — the dispatch proceeds either way; the
point is that a silent 3.5 s stall in the serving loop becomes a warning
with a program name attached.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Set

import numpy as np

from deepspeed_tpu.utils.logging import logger


def abstract_signature(args):
    """Per-leaf (shape, dtype, sharding, committed) tuples for an argument
    pytree — the same view ``fingerprint`` hashes, kept structured so a
    verifier (tools/tpuverify) can inspect which leaves entered a program
    and how they were placed. Non-array leaves record (type, repr)."""
    import jax
    sig = []
    for x in jax.tree_util.tree_leaves(args):
        if hasattr(x, "shape") and hasattr(x, "dtype"):
            sig.append({
                "shape": tuple(np.shape(x)),
                "dtype": str(x.dtype),
                "sharding": getattr(x, "sharding", None),
                "committed": bool(getattr(x, "_committed", False)),
            })
        else:
            sig.append({"static": (type(x).__name__, repr(x)[:64])})
    return sig


def abstract_args(args):
    """Structure-preserving abstract copy of an argument pytree: shaped
    leaves become ShapeDtypeStructs (carrying their NamedSharding only when
    the leaf was committed — uncommitted placement is not part of the
    program's contract), everything else passes through. The result can be
    fed back to ``jitted.lower(...)``/``jax.make_jaxpr`` chip-free, which
    is how tools/tpuverify re-derives a dispatched program's jaxpr."""
    import jax

    def one(x):
        if hasattr(x, "shape") and hasattr(x, "dtype"):
            sh = getattr(x, "sharding", None) \
                if getattr(x, "_committed", False) else None
            return jax.ShapeDtypeStruct(tuple(np.shape(x)), x.dtype,
                                        sharding=sh)
        return x

    return jax.tree_util.tree_map(one, args)


def signature_items(args) -> tuple:
    """The jit-cache-relevant signature of an argument pytree as a tuple
    of per-leaf tuples: (shape, dtype, sharding-repr, committed) for array
    leaves, (type, repr) for static leaves. ``fingerprint`` hashes this;
    the detector keeps each program's FIRST items so a later miss can name
    WHICH component drifted (``_diff_signature``)."""
    import jax
    sig = []
    for x in jax.tree_util.tree_leaves(args):
        if hasattr(x, "shape") and hasattr(x, "dtype"):
            sh = getattr(x, "sharding", None)
            sig.append((tuple(np.shape(x)), str(x.dtype),
                        repr(sh) if sh is not None else None,
                        bool(getattr(x, "_committed", False))))
        else:
            sig.append((type(x).__name__, repr(x)[:64]))
    return tuple(sig)


def fingerprint(args) -> int:
    """Hash of the jit-cache-relevant signature of an argument pytree:
    per-leaf (shape, dtype, sharding, committed). Non-array leaves hash by
    type+repr (static scalars / NVMeRef placeholders)."""
    return hash(signature_items(args))


_SIG_COMPONENTS = ("shape", "dtype", "sharding", "committed")


def _diff_signature(ref, cur) -> list:
    """Which signature components differ between a program's first-seen
    signature and a missing one — the recompile triage answer ('the cache
    leaves came back with a different sharding repr') that a bare miss
    warning makes needlessly slow to reconstruct on the chip."""
    if ref is None:
        return ["unknown"]
    if len(ref) != len(cur):
        return ["structure"]
    changed = set()
    for a, b in zip(ref, cur):
        if a == b:
            continue
        if len(a) != 4 or len(b) != 4:  # static leaf (type, repr) pair
            changed.add("static")
            continue
        for i, name in enumerate(_SIG_COMPONENTS):
            if a[i] != b[i]:
                changed.add(name)
    return sorted(changed) or ["none"]


class RecompileDetector:
    """Per-program signature tracking.

    First signature for a program name = the expected compile; every LATER
    new signature = a cache miss (recompile). ``observe`` returns True on a
    miss. ``pinned`` programs additionally log a warning per miss.
    """

    def __init__(self, name: str = "programs", hub=None,
                 pinned_default: bool = False):
        self.name = name
        self._hub = hub
        self.pinned_default = pinned_default
        self._seen: Dict[str, Set[int]] = {}
        # first-dispatch signature items per program — the diff baseline
        # for the `changed` field on miss events (tuples of small tuples;
        # one per program name, not per signature)
        self._first_items: Dict[str, tuple] = {}
        self.compiles = 0
        self.misses = 0
        self.pinned_misses = 0
        # Opt-in (tpuverify): keep the structured first-dispatch signature
        # per program so the pinned-sharding contract can be checked after a
        # smoke run. Off by default — zero overhead in the hot path.
        self.record_signatures = False
        self.signatures: Dict[str, list] = {}
        self.abstract: Dict[str, Any] = {}

    def _get_hub(self):
        if self._hub is not None:
            return self._hub
        from deepspeed_tpu.telemetry.hub import get_hub
        return get_hub()

    def observe(self, program: str, args: Any,
                pinned: Optional[bool] = None) -> bool:
        pinned = self.pinned_default if pinned is None else pinned
        items = signature_items(args)
        fp = hash(items)
        seen = self._seen.setdefault(program, set())
        if self.record_signatures and program not in self.signatures:
            self.signatures[program] = abstract_signature(args)
            self.abstract[program] = abstract_args(args)
        if fp in seen:
            return False
        first = not seen
        seen.add(fp)
        if first:
            self.compiles += 1
            self._first_items[program] = items
            return False
        self.misses += 1
        changed = _diff_signature(self._first_items.get(program), items)
        hub = self._get_hub()
        if pinned:
            self.pinned_misses += 1
            logger.warning(
                f"recompile detector [{self.name}]: pinned program "
                f"{program!r} saw a new (shape, dtype, sharding) signature "
                f"(changed: {', '.join(changed)} vs first dispatch) "
                f"— this dispatch recompiles (~3.5 s per serving program on "
                f"v5e, miss #{self.misses}). Pin cache/batch leaves with an "
                f"explicit device_put sharding to keep the compiled program "
                f"stable.")
            hub.counter("pinned_recompiles_total")
        hub.counter("recompiles_total")
        hub.emit("recompile", detector=self.name, program=program,
                 pinned=pinned, signatures=len(seen), misses=self.misses,
                 changed=changed)
        return True

    def stats(self) -> Dict[str, int]:
        return {"programs": len(self._seen), "compiles": self.compiles,
                "misses": self.misses, "pinned_misses": self.pinned_misses}
