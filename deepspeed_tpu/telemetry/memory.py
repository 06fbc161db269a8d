"""MemoryPlane: the tiered runtime residency ledger.

Every placement path registers its at-rest bytes here — named allocations
``{component, tier, bytes, owner}`` — so "where is every byte right now"
has a runtime answer instead of a hand-derived one (the r6 int8
7.63-vs-7.10 GB mismatch and an r5 phase-order leak were both found
by hand).

Design rules (load-bearing, mirrored in docs/memory.md):

- Bytes come from shapes / ``nbytes`` metadata ONLY — registering an
  allocation never fetches device data and never syncs (the
  no-hot-loop-fetch lint rule polices the dispatch loops).
- Registration happens at PLACEMENT/BUILD time (place_params, runner
  construction, state init, program dispatch), never inside per-token or
  per-layer streaming loops.
- Tiers are physical: ``hbm`` / ``host_pinned`` / ``host`` / ``nvme``.
  Components are semantic: ``params`` / ``opt_state`` / ``kv_cache`` /
  ``recurrent_state`` / ``staging`` / ``workspace`` / ``spec_draft``.
- ``logical=True`` allocations (e.g. KV block-manager occupancy, a view
  into an already-registered physical cache) appear in snapshots but are
  EXCLUDED from tier totals and watermarks — physical reconciliation
  against ``memory_stats()`` must not double count.
- Events are append-only hub kinds: ``memory_snapshot`` (on demand / at
  phase boundaries), ``memory_watermark`` (a tier total sets a new peak),
  ``residency_reconcile`` (registered-vs-predicted closure). Schemas in
  docs/telemetry.md.

Owners scope an engine's (or a runner's) allocations so degradation
re-placement can drop the whole set first — the r5 2×-residency lesson
applied to accounting: release before re-register, never accumulate.
"""

from __future__ import annotations

import contextlib
import itertools
import threading
import weakref
from dataclasses import dataclass
from typing import Any, Dict, Iterable, List, Optional, Tuple

COMPONENTS = ("params", "opt_state", "kv_cache", "recurrent_state", "staging",
              "workspace", "spec_draft")
TIERS = ("hbm", "host_pinned", "host", "nvme")

_OWNER_COUNTER = itertools.count()


def _release_on_gc(tag: str) -> None:
    try:
        get_plane().release_owner(tag)
    except Exception:
        pass


def owner_for(obj: Any, prefix: str) -> str:
    """Deterministic-per-process owner tag for ``obj`` (assigned once,
    stored on the object as ``_memory_owner``). A weakref finalizer drops
    the owner's allocations when the object is collected, so registered
    bytes track LIVE placements: a torn-down engine releases its rows."""
    tag = getattr(obj, "_memory_owner", None)
    if tag is None:
        tag = f"{prefix}:{next(_OWNER_COUNTER)}"
        try:
            obj._memory_owner = tag
            weakref.finalize(obj, _release_on_gc, tag)
        except (AttributeError, TypeError):
            pass
    return tag


# ------------------------------------------------------------- byte math


def leaf_bytes(leaf: Any) -> int:
    """At-rest bytes of one leaf from METADATA only (no device fetch):
    ``nbytes`` when present (np/jax arrays, _NVMeLeaf stand-ins), else
    shape×itemsize (ShapeDtypeStruct, NVMeRef placeholders), else 0 for
    non-array leaves (python scalars, None, static config)."""
    nbytes = getattr(leaf, "nbytes", None)
    if nbytes is not None and not callable(nbytes):
        try:
            return int(nbytes)
        except (TypeError, ValueError):
            pass
    shape = getattr(leaf, "shape", None)
    dtype = getattr(leaf, "dtype", None)
    if shape is not None and dtype is not None:
        import numpy as np
        size = 1
        for d in shape:
            size *= int(d)
        return size * int(np.dtype(dtype).itemsize)
    return 0


def tree_bytes(tree: Any) -> int:
    """Sum of ``leaf_bytes`` over a pytree (quantized ``{__q8__, scales}``
    dicts flatten to their arrays; NVMeRef leaves are not pytree leaves
    jax knows, so flatten with an is_leaf that keeps shaped objects)."""
    import jax

    def is_leaf(x):
        return getattr(x, "shape", None) is not None or x is None

    leaves = jax.tree_util.tree_leaves(tree, is_leaf=is_leaf)
    return sum(leaf_bytes(x) for x in leaves)


def _default_memory_kind(sharding: Any) -> Optional[str]:
    """The DEFAULT memory kind of the sharding's backend (TPU: 'device';
    the CPU test mesh: 'unpinned_host'). Cached per device kind."""
    try:
        dev = next(iter(sharding.device_set))
    except Exception:
        return None
    key = getattr(dev, "device_kind", None) or getattr(dev, "platform", "")
    if key not in _DEFAULT_KIND_CACHE:
        try:
            _DEFAULT_KIND_CACHE[key] = dev.default_memory().kind
        except Exception:
            _DEFAULT_KIND_CACHE[key] = None
    return _DEFAULT_KIND_CACHE[key]


_DEFAULT_KIND_CACHE: Dict[str, Optional[str]] = {}


def tier_of_sharding(sharding: Any) -> str:
    """Physical tier of a placed array's sharding. jax spells host tiers
    via ``memory_kind`` (``pinned_host`` / ``unpinned_host``) — but the
    backend's DEFAULT kind is the accelerator-resident tier whatever it
    is named (TPU calls it 'device'; the CPU test mesh's default is
    'unpinned_host', which must still read as the device tier or every
    CPU-mesh reconciliation test would see zero 'hbm' bytes)."""
    kind = getattr(sharding, "memory_kind", None)
    if kind is None or kind == _default_memory_kind(sharding):
        return "hbm"
    if kind == "pinned_host":
        return "host_pinned"
    if kind in ("unpinned_host", "host"):
        return "host"
    return "hbm"


def tier_of_leaf(leaf: Any) -> str:
    """Tier of one placed leaf: NVMeRef/parked placeholders are ``nvme``;
    numpy arrays are ``host``; jax Arrays follow their sharding."""
    cls = type(leaf).__name__
    if cls in ("NVMeRef", "_NVMeLeaf"):
        return "nvme"
    sharding = getattr(leaf, "sharding", None)
    if sharding is not None:
        return tier_of_sharding(sharding)
    import numpy as np
    if isinstance(leaf, np.ndarray):
        return "host"
    return "hbm"


# ----------------------------------------------------------- allocations


@dataclass
class Allocation:
    name: str
    component: str
    tier: str
    nbytes: int
    owner: str
    logical: bool = False


class MemoryPlane:
    """The process residency ledger. All methods are host-side dict ops
    under one lock (the capacity host loop and the swapper worker thread
    both register); nothing here touches device data."""

    def __init__(self, emit_events: bool = True):
        self._lock = threading.RLock()
        self._allocs: Dict[str, Allocation] = {}
        self._peaks: Dict[str, int] = {}
        self._owner_peaks: Dict[Tuple[str, str], int] = {}
        self.emit_events = emit_events

    # -- mutation ------------------------------------------------------

    def register(self, name: str, *, component: str, tier: str,
                 nbytes: Optional[int] = None, tree: Any = None,
                 owner: str = "global", logical: bool = False) -> int:
        """Record (or replace — same name overwrites) one allocation.
        Returns the registered byte count."""
        if component not in COMPONENTS:
            raise ValueError(f"unknown component {component!r} "
                             f"(known: {COMPONENTS})")
        if tier not in TIERS:
            raise ValueError(f"unknown tier {tier!r} (known: {TIERS})")
        if nbytes is None:
            nbytes = tree_bytes(tree) if tree is not None else 0
        nbytes = int(nbytes)
        with self._lock:
            self._allocs[name] = Allocation(name=name, component=component,
                                            tier=tier, nbytes=nbytes,
                                            owner=owner, logical=logical)
            self._note_peaks(tier, owner)
        return nbytes

    def register_tree(self, name: str, *, component: str, tree: Any,
                      owner: str = "global") -> Dict[str, int]:
        """Register a placed pytree split BY TIER (one allocation per tier
        present): leaves route via ``tier_of_leaf``. Returns the per-tier
        byte map."""
        import jax

        def is_leaf(x):
            return getattr(x, "shape", None) is not None or x is None

        per_tier: Dict[str, int] = {}
        for leaf in jax.tree_util.tree_leaves(tree, is_leaf=is_leaf):
            b = leaf_bytes(leaf)
            if not b:
                continue
            t = tier_of_leaf(leaf)
            per_tier[t] = per_tier.get(t, 0) + b
        for t, b in per_tier.items():
            self.register(f"{name}@{t}", component=component, tier=t,
                          nbytes=b, owner=owner)
        return per_tier

    def adjust(self, name: str, delta: int, *, component: str, tier: str,
               owner: str = "global", logical: bool = False) -> int:
        """Add ``delta`` bytes to a running allocation (creating it at the
        given identity if absent; floored at 0). For accumulating sites
        like NVMe swap-out streams."""
        with self._lock:
            cur = self._allocs.get(name)
            base = cur.nbytes if cur is not None else 0
            return self.register(name, component=component, tier=tier,
                                 nbytes=max(0, base + int(delta)),
                                 owner=owner, logical=logical)

    def release(self, name: str) -> None:
        with self._lock:
            self._allocs.pop(name, None)

    def release_owner(self, owner: str) -> None:
        """Drop every allocation of one owner — placement paths call this
        FIRST on re-placement (degradation ladder) so accounting never
        double-counts a replaced tree."""
        with self._lock:
            for k in [k for k, a in self._allocs.items()
                      if a.owner == owner]:
                del self._allocs[k]

    def reset(self) -> None:
        with self._lock:
            self._allocs.clear()
            self._peaks.clear()
            self._owner_peaks.clear()

    # -- queries -------------------------------------------------------

    def total(self, tier: Optional[str] = None,
              component: Optional[str] = None,
              owner: Optional[str] = None) -> int:
        """Physical bytes matching the filters (logical rows excluded)."""
        with self._lock:
            return sum(a.nbytes for a in self._allocs.values()
                       if not a.logical
                       and (tier is None or a.tier == tier)
                       and (component is None or a.component == component)
                       and (owner is None or a.owner == owner))

    def tier_totals(self, owner: Optional[str] = None) -> Dict[str, int]:
        out = {t: 0 for t in TIERS}
        with self._lock:
            for a in self._allocs.values():
                if a.logical or (owner is not None and a.owner != owner):
                    continue
                out[a.tier] += a.nbytes
        return out

    def component_totals(self, owner: Optional[str] = None
                         ) -> Dict[str, Dict[str, int]]:
        """{tier: {component: bytes}} over physical rows."""
        out: Dict[str, Dict[str, int]] = {}
        with self._lock:
            for a in self._allocs.values():
                if a.logical or (owner is not None and a.owner != owner):
                    continue
                out.setdefault(a.tier, {})
                out[a.tier][a.component] = \
                    out[a.tier].get(a.component, 0) + a.nbytes
        return out

    def watermark(self, tier: str, owner: Optional[str] = None) -> int:
        """Peak physical bytes ever registered for the tier (optionally
        scoped to one owner) since the last ``reset``."""
        with self._lock:
            if owner is None:
                return self._peaks.get(tier, 0)
            return self._owner_peaks.get((owner, tier), 0)

    def allocations(self) -> List[Allocation]:
        with self._lock:
            return list(self._allocs.values())

    def snapshot(self) -> Dict[str, Any]:
        """JSON-ready residency summary: per-tier physical totals +
        watermarks, {tier: {component: bytes}} breakdown, and the logical
        rows (occupancy views) listed separately."""
        with self._lock:
            logical = {a.name: a.nbytes for a in self._allocs.values()
                       if a.logical}
            return {
                "tiers": self.tier_totals(),
                "watermarks": {t: self._peaks.get(t, 0) for t in TIERS
                               if self._peaks.get(t, 0)},
                "components": self.component_totals(),
                "logical": logical,
                "n_allocations": len(self._allocs),
            }

    # -- events --------------------------------------------------------

    def _note_peaks(self, tier: str, owner: str) -> None:
        # under self._lock
        total = sum(a.nbytes for a in self._allocs.values()
                    if not a.logical and a.tier == tier)
        okey = (owner, tier)
        if total > self._owner_peaks.get(okey, 0):
            self._owner_peaks[okey] = total
        if total > self._peaks.get(tier, 0):
            self._peaks[tier] = total
            if self.emit_events:
                self._emit("memory_watermark", tier=tier, peak_bytes=total)

    @staticmethod
    def _emit(kind: str, **fields) -> None:
        from deepspeed_tpu.telemetry.hub import get_hub
        get_hub().emit(kind, **fields)

    def emit_snapshot(self, reason: str, step: Optional[int] = None,
                      **extra) -> Dict[str, Any]:
        """Emit a ``memory_snapshot`` event (and return the snapshot).
        ``extra`` may carry accelerator ``memory_stats`` numbers at phase
        boundaries for the on-chip registered-vs-measured check."""
        snap = self.snapshot()
        if self.emit_events:
            self._emit("memory_snapshot", step=step, reason=reason,
                       residency=snap, **extra)
        return snap

    def reconcile(self, check: str, predicted_bytes: int, *,
                  tier: str = "hbm", owner: Optional[str] = None,
                  component: Optional[str] = None,
                  tolerance: float = 0.02) -> Dict[str, Any]:
        """Close the loop: registered bytes vs a formula prediction
        (CapacityPlan.peak_hbm_bytes, kv_cache_bytes/KVBudget, the int8
        weight accounting). Emits ``residency_reconcile`` and returns
        {registered_bytes, predicted_bytes, drift, ok}."""
        registered = self.total(tier=tier, component=component, owner=owner)
        predicted_bytes = int(predicted_bytes)
        denom = max(predicted_bytes, 1)
        drift = (registered - predicted_bytes) / denom
        ok = abs(drift) <= tolerance
        result = {"check": check, "tier": tier,
                  "registered_bytes": registered,
                  "predicted_bytes": predicted_bytes,
                  "drift": drift, "ok": ok}
        if self.emit_events:
            self._emit("residency_reconcile", check=check, tier=tier,
                       owner=owner, registered_bytes=registered,
                       predicted_bytes=predicted_bytes, drift=drift, ok=ok,
                       tolerance=tolerance)
        return result


# ---------------------------------------------------------- global plane

_PLANE = MemoryPlane()


def get_plane() -> MemoryPlane:
    return _PLANE


def set_plane(plane: MemoryPlane) -> MemoryPlane:
    global _PLANE
    prev, _PLANE = _PLANE, plane
    return prev


@contextlib.contextmanager
def scratch_plane(emit_events: bool = True):
    """Swap in a fresh plane (tests / the tpuverify matrix), restore on
    exit."""
    plane = MemoryPlane(emit_events=emit_events)
    prev = set_plane(plane)
    try:
        yield plane
    finally:
        set_plane(prev)
